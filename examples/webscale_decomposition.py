"""Web-scale-style decomposition: on-disk graph, pluggable compute backend,
sharded mesh execution, checkpoint/restart.

The end-to-end driver for the paper's workload: builds an RMAT web-crawl-like
graph, stores it as the on-disk node/edge tables, decomposes it with the
semi-external host engine on the chosen compute backend (DESIGN.md §11),
cross-checks the sharded mesh backend, checkpoints mid-run, and proves a warm
restart converges to the same fixpoint (monotone upper bounds = free crash
consistency).

    PYTHONPATH=src python examples/webscale_decomposition.py \
        [--backend numpy|xla|pallas|shard] [--num-shards N]

``--backend pallas`` demonstrates the paper's block skipping at the kernel
layer end to end: SemiCore*'s shrinking frontier drives the block-activity
flags of the fused superstep kernel, so untouched edge blocks issue no DMA.
The kernel is compiled by Mosaic on a TPU and interpreted on CPU, where the
graph is scaled down to keep the demo quick.

``--backend shard`` runs the whole fixpoint on a device mesh (DESIGN.md §13):
per-device contiguous edge shards, replicated O(n) core, one all_gather of
owned slices per superstep — with the exact numpy pass/I-O trace.  Force
more host devices to see a real mesh on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/webscale_decomposition.py \
        --backend shard --num-shards 8
"""
import argparse
import os
import tempfile
import time

import numpy as np

from repro.graph import rmat, CSRGraph
from repro.core import imcore_peel, decompose
from repro.core.distributed import distributed_decompose
from repro.core.engine import ShardedBackend
from repro.train import save, restore

parser = argparse.ArgumentParser()
parser.add_argument("--backend", default="numpy",
                    choices=["numpy", "xla", "pallas", "shard"],
                    help="batch-schedule compute backend (DESIGN.md §11/§13)")
parser.add_argument("--num-shards", type=int, default=None,
                    help="mesh width for --backend shard "
                    "(CoreGraphConfig.num_shards; default: all devices)")
args = parser.parse_args()

workdir = tempfile.mkdtemp(prefix="webscale_")

# 1) build + store the graph on disk (the paper's edge/node tables).
# Interpret-mode pallas pays a Python-level cost per kernel block, so the
# pallas demo uses a smaller crawl + coarser blocks.
if args.backend == "pallas":
    scale, edge_factor, block_edges = 13, 8, 512
else:
    scale, edge_factor, block_edges = 17, 12, 4096
g = rmat(scale, edge_factor, seed=3)
g.save(os.path.join(workdir, "graph"))
g = CSRGraph.load(os.path.join(workdir, "graph"), mmap=True)  # edges on disk
print(f"graph: n={g.n:,} 2m={g.num_directed:,} (memmapped from disk)")

# 2) host OOC engine (the faithful semi-external reproduction) on the
#    selected compute backend.  Device backends run the fixpoint
#    device-resident (DESIGN.md §12): the edge table uploads once, ~8 fused
#    passes execute per host round-trip, and jit compiles stay O(1) per
#    decompose — resident.trace_count() below proves it.  The shard backend
#    keeps the same contract with the edge table cut over the mesh (§13).
from repro.core import resident
backend = (ShardedBackend(num_shards=args.num_shards)
           if args.backend == "shard" else args.backend)
traces0 = resident.trace_count()
t0 = time.time()
r = decompose(g, "semicore*", "batch", block_edges=block_edges,
              backend=backend)
print(f"SemiCore* (OOC host, backend={r.backend}): kmax={r.kmax} "
      f"iters={r.iterations} I/O={r.edge_block_reads} blocks in "
      f"{time.time() - t0:.2f}s; node-state memory {r.memory_bytes / 1e6:.1f} MB")
if args.backend != "numpy" and resident.resident_enabled():
    print(f"  device-resident: {resident.trace_count() - traces0} jit "
          f"trace(s) for {r.iterations} passes "
          f"(~{-(-r.iterations // resident.chunk_len())} host round-trips)")
if args.backend == "pallas":
    total = r.kernel_blocks_active + r.kernel_blocks_skipped
    print(f"  kernel layer: {r.kernel_blocks_skipped}/{total} edge-block DMAs "
          f"skipped by the frontier activity mask (SemiCore* I/O saving)")
if args.backend == "shard":
    print(f"  mesh: {r.num_shards} shard(s), rectangular-layout padding "
          f"{r.shard_pad_edges} edge slots "
          f"({100.0 * r.shard_pad_edges / max(1, g.num_directed):.1f}% "
          f"of 2m — minimax-balanced contiguous cuts)")
expect = imcore_peel(g)
assert np.array_equal(r.core, expect)

# 3) sharded mesh engine + mid-run checkpoint/restart
core, iters = distributed_decompose(g)
assert np.array_equal(core, expect)
print(f"shard engine: {iters} supersteps — matches IMCore")

# simulate a crash: run a budgeted prefix (chunk-granular), checkpoint the
# intermediate state — any superstep's core is a valid upper bound — and
# restart warm from it
budget = max(2, iters // 2)
partial_core, done = distributed_decompose(g, max_supersteps=budget)
save(workdir, int(done), {"core": np.asarray(partial_core, dtype=np.int32)})
print(f"checkpointed after {int(done)} supersteps (upper bounds still valid)")

(state, step) = restore(workdir, {"core": np.zeros(g.n, np.int32)})
core2, extra = distributed_decompose(g, core0=state["core"])
assert np.array_equal(core2, expect)
print(f"warm restart finished in {extra} further supersteps — exact result")
