"""Pallas TPU kernels (compiled by Mosaic on TPU, interpreted on CPU).

segsum         -- blocked one-hot-matmul segment sum (edge scans: LocalCore
                  counts, GNN aggregation, bag pooling)
embedding_bag  -- scalar-prefetch gather-pool (recsys tables)
flash_decode   -- blocked long-KV decode attention (long_500k cells)
fused_superstep-- the decomposition superstep's histogram kernel (h-index,
                  cnt refresh, neighbour counts) with activity-masked block
                  DMA (DESIGN.md §16)

``resolve_interpret`` is the single interpret-mode policy: the platform
decides.  Kernels are interpreted on CPU, where nothing else can run them,
and compiled everywhere else.  A request for the other mode — an explicit
``interpret=`` or ``REPRO_PALLAS_INTERPRET`` — raises instead of quietly
emulating on an accelerator.
"""
from __future__ import annotations

__all__ = ["segment_sum", "segment_sum_active", "embedding_bag",
           "flash_decode", "resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode exactly when JAX's default platform is the CPU.
    ``interpret`` or ``REPRO_PALLAS_INTERPRET``, where given, must agree."""
    import jax

    from repro import runtime as _runtime

    mode = jax.default_backend() == "cpu"
    want = interpret if interpret is not None else \
        _runtime.setting("pallas_interpret")
    if want is not None and bool(want) != mode:
        raise ValueError(
            f"Pallas interpret={bool(want)} requested on platform "
            f"{jax.default_backend()!r}, which runs kernels "
            f"{'interpreted' if mode else 'compiled'} only")
    return mode


# Bound eagerly (as the functions, not the same-named submodules — the
# function binding must shadow e.g. the embedding_bag module).  This import
# sits *below* resolve_interpret because the kernel modules resolve their
# ``interpret=None`` defaults through this package at call time.
from .ops import segment_sum, segment_sum_active, embedding_bag, flash_decode  # noqa: E402
