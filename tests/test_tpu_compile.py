"""Ahead-of-time compiles of the main path for a TPU v5e, without a chip.

The TPU compiler ships with JAX here, so each test lowers one kernel or jit
at a real size for a described (not attached) ``v5e:2x2`` topology and lets
the compiler refuse what the chip would: Mosaic lowering rules, VMEM and
SMEM budgets, HBM fit, mesh partitioning.  The topology is described inside
a module fixture, so collecting this file never loads the TPU library; the
fixture skips where the topology cannot be described.  Nothing here runs on
a device, so nothing here says anything about results or times.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import resident
from repro.graph import chung_lu
from repro.kernels import fused_superstep as fsk

# SNAP soc-LiveJournal1 as chip_smoke.py generates it: n nodes, ~2m directed
# edges, and the probe count of its max degree (chung_lu: about 7k)
LJ_N = 4_847_571
LJ_E = 2 * 68_993_773
LJ_PROBES = 13
HBM_BYTES = 16 * 10 ** 9
ALGORITHMS = ("semicore", "semicore+", "semicore*")


@pytest.fixture(scope="module")
def topo():
    prior_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs to /tmp
    prior_cache = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior_cache)
    if prior_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prior_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bench_small():
    """The fused table of the bench small cell (bench_backends.py)."""
    g = chung_lu(1200, 4800, seed=0)
    probes = math.ceil(math.log2(int(np.diff(g.indptr).max()) + 2))
    return fsk.build_fused_table(g.indptr, g.adj, g.n,
                                 fsk.fused_block_edges()), probes


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _vec(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_fused_pass_compiles_for_v5e(algorithm, one_chip, bench_small):
    ft, probes = bench_small
    n = ft.dims[4]
    step = jax.jit(fsk.fused_pass, static_argnames=(
        "dims", "num_probes", "algorithm", "interpret"))
    compiled = step.lower(
        _vec(n, jnp.int32, one_chip), _vec(n, jnp.int32, one_chip),
        _vec(n, jnp.bool_, one_chip), _shapes(ft.arrays, one_chip),
        dims=ft.dims, num_probes=probes, algorithm=algorithm,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_counts_compiles_for_v5e(one_chip, bench_small):
    ft, _ = bench_small
    n = ft.dims[4]
    compiled = fsk.fused_counts.lower(
        _vec(n, jnp.int32, one_chip), _vec(n, jnp.int32, one_chip),
        _vec(n, jnp.bool_, one_chip), _shapes(ft.arrays, one_chip),
        dims=ft.dims, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _synthetic_dims(fraction, num_probes):
    """Fused-kernel dims whose VMEM need is ``fraction`` of the limit."""
    cbe = fsk.fused_block_edges()
    nwin = (cbe + 126) // 128 + 1
    fixed = fsk.vmem_bytes((cbe, 1, nwin, 0, 0, 0), num_probes)
    per_group = fsk.vmem_bytes((cbe, 1, nwin, 1, 0, 0), num_probes) - fixed
    Rb = int((fraction * fsk.VMEM_LIMIT_BYTES - fixed) // per_group)
    n = (Rb - nwin) * 128
    nbk = 4 * n // cbe
    return (cbe, nbk, nwin, Rb, n, nbk * cbe)


@pytest.mark.parametrize("side", ["admitted", "refused"])
def test_fused_kernel_memory_guard(side, one_chip):
    """Just under the guard the kernel compiles (the guard does not
    undercount); past it the guard raises before the compiler runs."""
    probes = 10
    dims = _synthetic_dims(0.97 if side == "admitted" else 1.03, probes)
    cbe, nbk = dims[0], dims[1]
    args = (_vec(nbk, jnp.int32, one_chip), _vec(nbk, jnp.int32, one_chip),
            jax.ShapeDtypeStruct((nbk, 1, cbe), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((nbk, 1, cbe), jnp.int32, sharding=one_chip))
    if side == "admitted":
        fn = fsk._hist_call(dims, probes, False)
        assert "tpu_custom_call" in jax.jit(fn).lower(*args) \
            .compile().as_text()
    else:
        with pytest.raises(ValueError, match="VMEM"):
            fsk._hist_call(dims, probes, False)


def test_xla_chunk_compiles_at_lj_shape(one_chip):
    E_pad = resident._edge_pad(LJ_E)
    fn = resident._chunk_fns("xla", 0, False, "semicore*")
    compiled = fn.lower(
        _vec(LJ_N, jnp.int32, one_chip), _vec(LJ_N, jnp.int32, one_chip),
        _vec(LJ_N, jnp.bool_, one_chip), _vec(E_pad, jnp.int32, one_chip),
        _vec(LJ_N + 1, jnp.int32, one_chip),
        num_probes=LJ_PROBES, num_segments=LJ_N,
        chunk=resident.chunk_len()).compile()
    assert _fits_hbm(compiled)


def test_shard_chunk_compiles_on_v5e_2x2(topo):
    S = 4
    mesh = Mesh(np.asarray(topo.devices[:S]), ("shard",))
    V = -(-LJ_N // S) + 1
    Emax = -(-LJ_E // S) + 8192
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("shard"))

    def sharded(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)

    fn = resident.build_shard_chunk_fn(mesh, "semicore*", LJ_N, LJ_PROBES)
    compiled = fn.lower(
        _vec(LJ_N, jnp.int32, repl), sharded((S, V), jnp.int32),
        sharded((S, V), jnp.bool_), jax.ShapeDtypeStruct((), jnp.int32,
                                                         sharding=repl),
        sharded((S, Emax), jnp.int32), sharded((S, Emax), jnp.bool_),
        sharded((S, V + 1), jnp.int32),
        sharded((S, V), jnp.int32), sharded((S, V), jnp.bool_)).compile()
    # the per-superstep all_gather of core (the compiler may lower it as
    # an all-reduce)
    assert re.search(r"all-(gather|reduce)", compiled.as_text())
    assert _fits_hbm(compiled)
