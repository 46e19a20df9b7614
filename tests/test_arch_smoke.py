"""Per-arch smoke tests: reduced configs, one real step on CPU per shape cell.

Asserts output shapes + finiteness for all 10 assigned archs x their 4 shapes
(40 cells, reduced sizes) + the paper's own decompose cell.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config, shape_names, ARCH_IDS
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_step

CELLS = []
for arch in ARCH_IDS:
    for shape in shape_names(get_config(arch)):
        CELLS.append((arch, shape))


def materialize(avals, cfg, rng):
    """Random concrete inputs from ShapeDtypeStruct trees, domain-aware."""
    def gen(path, s):
        name = path[-1] if path else ""
        shape, dtype = s.shape, s.dtype
        if dtype == jnp.int32:
            hi = 4
            if name in ("tokens", "labels") and cfg.kind == "lm":
                hi = cfg.vocab
            elif name in ("hist_ids", "target_id", "negative_ids",
                          "candidate_ids"):
                hi = cfg.n_items
            elif name == "profile_ids":
                hi = cfg.profile_vocab
            elif name == "z":
                hi = 90
            elif name == "len":
                return jnp.zeros((), jnp.int32)
            elif name in ("src", "dst"):
                hi = gen.num_nodes
            elif name == "labels":
                hi = max(getattr(cfg, "num_classes", 4), 2)
            elif name == "graph_ids":
                n = shape[0]
                g = gen.num_graphs
                return jnp.asarray(np.repeat(np.arange(g), n // g)[:n], jnp.int32)
            return jnp.asarray(rng.integers(0, max(hi, 1), size=shape), jnp.int32)
        if dtype == jnp.bool_:
            return jnp.asarray(rng.random(shape) < 0.9)
        return jnp.asarray(rng.normal(size=shape) * 0.1, dtype)

    gen.num_nodes = None
    gen.num_graphs = 1

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, jax.ShapeDtypeStruct):
            return gen(path, tree)
        return tree

    return walk, gen


@pytest.mark.parametrize("arch,shape", CELLS)
def test_cell_smoke(arch, shape):
    mesh = make_host_mesh()
    bundle = build_step(arch, shape, mesh, reduced=True)
    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(42)
    walk, gen = materialize(None, cfg, rng)

    # find num_nodes for GNN cells (for src/dst ranges)
    if cfg.kind == "gnn":
        from repro.configs import input_specs
        _, av = input_specs(cfg, shape, reduced=True)
        gen.num_nodes = av["num_nodes"]
        if shape == "molecule":
            gen.num_graphs = 4

    args = list(walk(a) for a in bundle.args)
    if bundle.name == "train_step":
        # optimizer state must be *initialized*, not randomized (v >= 0)
        from repro.optim import adamw_init
        args[1] = adamw_init(args[0], bundle.static["opt"])
    args = tuple(args)
    fn = (jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                  out_shardings=bundle.out_shardings,
                  donate_argnums=bundle.donate_argnums)
          if bundle.in_shardings is not None else bundle.fn)
    with jax.set_mesh(mesh):
        out = fn(*args)

    leaves = jax.tree.leaves(out)
    assert leaves, "no outputs"
    for leaf in leaves:
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f":
            assert np.isfinite(arr).all(), f"{arch}/{shape}: non-finite output"

    if bundle.name == "train_step":
        loss = float(np.asarray(leaves[-1]).reshape(-1)[0])
        assert np.isfinite(loss)


def test_semicore_webscale_reduced_cell():
    """The paper's own cell at reduced scale executes end-to-end."""
    from repro.graph import chung_lu
    from repro.core.imcore import imcore_peel
    from repro.core.distributed import distributed_decompose

    g = chung_lu(2000, 16000, seed=0)
    core, iters = distributed_decompose(g)
    np.testing.assert_array_equal(core, imcore_peel(g))
