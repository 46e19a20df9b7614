"""Pallas TPU kernel: segment-sum with *block skipping* — the semi-external
I/O saving (SemiCore+/SemiCore*, §IV-B/C) expressed at the HBM->VMEM level.

The paper skips disk blocks whose nodes cannot update; here a scalar-prefetched
per-block activity flag drives the BlockSpec ``index_map``: inactive blocks
map to block 0, which is already VMEM-resident after the first step, so the
pipeline issues **no DMA** for them — skipped I/O on TPU, block-for-block the
paper's discipline.  The kernel body is additionally predicated with
``pl.when`` so skipped blocks cost neither bandwidth nor MXU cycles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(active_ref, compact_ref, vals_ref, out_ref, *, block_edges: int):
    b = pl.program_id(0)

    @pl.when(active_ref[b] > 0)
    def _compute():
        c = compact_ref[...]                    # (BE, 1)
        vals = vals_ref[...]                    # (BE, D)
        first = c[0, 0]
        iota = jax.lax.broadcasted_iota(
            jnp.int32, (block_edges, block_edges), 1)
        onehot = ((c - first) == iota).astype(jnp.float32)
        out_ref[0] = jax.lax.dot_general(
            onehot, vals, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(active_ref[b] == 0)
    def _skip():
        out_ref[0] = jnp.zeros_like(out_ref[0])


def segsum_active_partials(
    vals: jax.Array,          # (E, D) float32, E % block_edges == 0
    compact: jax.Array,       # (E, 1) int32 dense sorted segment ranks
    block_active: jax.Array,  # (num_blocks,) int32 — 0 skips the block
    *,
    block_edges: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Window partials like segsum, but inactive blocks are never fetched.

    ``interpret`` -- None defers to ``kernels.resolve_interpret``.
    """
    from . import resolve_interpret
    interpret = resolve_interpret(interpret)
    E, D = vals.shape
    assert E % block_edges == 0
    nb = E // block_edges
    kernel = functools.partial(_kernel, block_edges=block_edges)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            # inactive blocks re-map to block 0: no new DMA is issued
            pl.BlockSpec((block_edges, 1),
                         lambda b, act: (jnp.where(act[b] > 0, b, 0), 0)),
            pl.BlockSpec((block_edges, D),
                         lambda b, act: (jnp.where(act[b] > 0, b, 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, block_edges, D), lambda b, act: (b, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, block_edges, D), jnp.float32),
        interpret=interpret,
    )(block_active.astype(jnp.int32), compact, vals)
