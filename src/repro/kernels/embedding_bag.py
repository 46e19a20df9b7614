"""Pallas TPU kernel: EmbeddingBag (gather + pooled reduce) via scalar prefetch.

JAX has no native EmbeddingBag; this is the TPU-native one (DESIGN.md §6):
the bag indices are *scalar-prefetched* so the input ``index_map`` can DMA
exactly the needed table rows HBM->VMEM (the canonical Pallas block-sparse
pattern) while the output block stays resident in VMEM across the bag axis
and accumulates.  The embedding table itself never materializes in VMEM —
only ``bag_size`` rows per output row, mirroring the paper's semi-external
contract (O(state) fast memory, stream the big table).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, table_row_ref, weight_ref, out_ref):
    l = pl.program_id(1)
    row = table_row_ref[...]          # (1, D) — the index-mapped table row
    w = weight_ref[...]               # (1, 1) — per-slot weight (0 = masked)
    contrib = row * w

    @pl.when(l == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(l > 0)
    def _acc():
        out_ref[...] += contrib


def embedding_bag_pallas(
    table: jax.Array,      # (N, D)
    indices: jax.Array,    # (B, L) int32; negative = masked slot
    weights: jax.Array,    # (B, L) float32 per-slot weights
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Sum-pooled bags: out[b] = sum_l weights[b,l] * table[indices[b,l]].

    ``interpret`` -- None defers to ``kernels.resolve_interpret``.
    """
    from . import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, L = indices.shape
    N, D = table.shape
    safe_idx = jnp.maximum(indices, 0).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L),
        in_specs=[
            pl.BlockSpec((1, D), lambda b, l, idx: (idx[b, l], 0)),
            pl.BlockSpec((1, 1), lambda b, l, idx: (b, l)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, l, idx: (b, 0)),
    )
    return pl.pallas_call(
        _bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=interpret,
    )(safe_idx, table, weights.astype(table.dtype))
