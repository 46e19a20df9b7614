"""Pallas TPU kernel: blocked segment-sum over sorted segment ids.

The universal edge-scan primitive of this framework (DESIGN.md §6): LocalCore
neighbor counts, GNN message aggregation, and embedding-bag pooling are all
segment-sums over a CSR-sorted edge axis.

TPU-native design: the grid marches fixed-size edge blocks HBM->VMEM
(``BlockSpec`` tiles — the semi-external "sequential block scan"), and the
scatter within a block is expressed as a one-hot x values **matmul** so the
MXU does the reduction.  Because segment ids are *compacted* (dense ranks),
a block of BE edges touches at most BE consecutive compact rows, so each
block's partial result is a (BE, D) window starting at the block's first
compact row; windows are combined by a cheap scatter-add epilogue in the
jit'd wrapper (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segsum_block_kernel(compact_ref, vals_ref, out_ref, *, block_edges: int):
    """One grid step: (BE, D) values -> (BE, D) window partial via MXU."""
    c = compact_ref[...]  # (BE, 1) int32 compact segment ids (sorted)
    vals = vals_ref[...]  # (BE, D)
    first = c[0, 0]
    # one-hot of (compact - first) against the BE-wide local window
    local = c - first  # (BE, 1), values in [0, BE)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_edges, block_edges), 1)
    onehot = (local == iota).astype(jnp.float32)  # (BE, W=BE)
    # MXU: window partial = onehot^T @ vals
    out_ref[0] = jax.lax.dot_general(
        onehot, vals,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def segsum_pallas_partials(
    vals: jax.Array, compact: jax.Array, *, block_edges: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Run the blocked kernel; returns (num_blocks, BE, D) window partials.

    ``vals``    -- (E, D) float32, E a multiple of block_edges.
    ``compact`` -- (E, 1) int32 dense sorted segment ranks.
    ``interpret`` -- None defers to ``kernels.resolve_interpret``.
    """
    from . import resolve_interpret
    interpret = resolve_interpret(interpret)
    E, D = vals.shape
    assert E % block_edges == 0, (E, block_edges)
    nb = E // block_edges
    kernel = functools.partial(_segsum_block_kernel, block_edges=block_edges)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_edges, 1), lambda b: (b, 0)),
            pl.BlockSpec((block_edges, D), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_edges, D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block_edges, D), jnp.float32),
        interpret=interpret,
    )(compact, vals)
