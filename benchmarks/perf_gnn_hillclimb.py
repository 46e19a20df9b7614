"""§Perf hillclimb C: the most collective-bound cell (GNN full-graph
aggregation at ogb_products scale).

Baseline: edges sharded over all axes, node state replicated; XLA lowers
``segment_sum`` into per-device partials + an all-reduce of the full (N, d)
message matrix (~2·N·d·4 B per chip per layer).

Optimized (the paper's layout, one level up): edges are *pre-partitioned by
destination stripe* (the contiguous node-range ownership of the decomposition
engine), so each device's partial lands only in its own stripe — no reduction
at all; the combine is a stripe all-gather (~1·N·d·4 B): predicted 2x less
ICI traffic, plus an (N,d)-sized scatter removed from the memory term.

Run (writes benchmarks/results/perf_gnn_hillclimb.json):
    PYTHONPATH=src python benchmarks/perf_gnn_hillclimb.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import json

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../src"))

from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.dryrun import _metrics, ICI_BW, HBM_BW  # noqa: E402

N = 2_449_029            # ogb_products nodes
D = 128                  # graphsage hidden
CHIPS = 256
E = 61_859_328           # padded directed edges
E_LOC = E // CHIPS
N_STRIPE = -(-N // CHIPS)


def run():
    mesh = make_production_mesh()
    axes = tuple(mesh.axis_names)
    sds = jax.ShapeDtypeStruct
    h = sds((N, D), jnp.float32)
    src = sds((E,), jnp.int32)
    dst = sds((E,), jnp.int32)

    # ---------------- baseline: auto-SPMD segment_sum + implicit all-reduce
    def baseline(h, src, dst):
        return jax.ops.segment_sum(jnp.take(h, src, axis=0), dst,
                                   num_segments=N)

    fb = jax.jit(
        baseline,
        in_shardings=(NamedSharding(mesh, P()),
                      NamedSharding(mesh, P(axes)),
                      NamedSharding(mesh, P(axes))),
        out_shardings=NamedSharding(mesh, P()),
    )
    with jax.set_mesh(mesh):
        mb = _metrics(fb.lower(h, src, dst).compile())

    # ------------- optimized: dst-striped edges -> local partial + all-gather
    def striped(h, src, dst, stripe_lo):
        lo = stripe_lo[0]  # 1-D edge arrays arrive pre-sliced per device
        local = jax.ops.segment_sum(
            jnp.take(h, src, axis=0), dst - lo, num_segments=N_STRIPE)
        out = jax.lax.all_gather(local, axes, tiled=True)  # (CHIPS*N_STRIPE, D)
        return out[:N]

    fs = jax.jit(jax.shard_map(
        striped, mesh=mesh,
        in_specs=(P(), P(axes), P(axes), P(axes)),
        out_specs=P(), check_vma=False,
    ), in_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P(axes)),
                     NamedSharding(mesh, P(axes)), NamedSharding(mesh, P(axes))),
       out_shardings=NamedSharding(mesh, P()))
    stripe_lo = sds((CHIPS,), jnp.int32)
    with jax.set_mesh(mesh):
        ms = _metrics(fs.lower(h, src, dst, stripe_lo).compile())

    rows = {}
    for name, m in [("baseline_allreduce", mb), ("striped_allgather", ms)]:
        rows[name] = {
            "bytes_per_chip": m["bytes"], "memory_s": m["bytes"] / HBM_BW,
            "collective_bytes": m["coll"], "collective_s": m["coll"]["total"] / ICI_BW,
        }
        print(f"{name}: HBM bytes %.3e (%.4f s)  ICI %.3e B (%.4f s)" % (
            m["bytes"], m["bytes"] / HBM_BW,
            m["coll"]["total"], m["coll"]["total"] / ICI_BW))
    out_path = os.path.join(os.path.dirname(__file__), "results",
                            "perf_gnn_hillclimb.json")
    json.dump(rows, open(out_path, "w"), indent=1)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    run()
