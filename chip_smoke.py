"""Bring-up smoke of the k-core service on a TPU: run it once, check it.

    python chip_smoke.py [--seed S] [--scale F]     # one chip
    python chip_smoke.py --shard [--seed S] [--scale F]   # four chips

Everything runs in this one process, which holds the chip; no child process
touches JAX.  Without ``--shard`` the phases are, in order:

1. device check: JAX's first device must be a TPU, else exit 1;
2. compile cache: ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``;
3. graph: ``chung_lu`` from ``--seed`` at the size of SNAP soc-LiveJournal1
   (the paper's LJ), scaled by ``--scale`` (default 0.05, printed as
   ``reduced``: on a TPU v5e an xla pass takes about 6.6 s at 0.25 and
   every maintenance batch settles in as many passes as a decompose, so the
   full size does not finish within 20 minutes);
4. ``decompose(g, "semicore*", "batch", backend="xla")``, which must equal
   the numpy backend exactly (core, cnt, passes and the I/O trace);
5. a ``CoreWriter`` on ``"xla"`` with a fsynced WAL ingests four mixed
   batches of 64 ops and answers coreness / top_k / degeneracy after each;
   every epoch's core and cnt must equal a numpy ``CoreMaintainer`` fed the
   same batches;
6. ``decompose`` on ``"pallas"`` with the kernel compiled, all three
   algorithms, at the bench small-cell size and at the largest graph the
   kernel's VMEM guard admits; each must equal numpy.

``--shard`` instead runs the decompose and two maintenance batches on a
4-way ``ShardedBackend`` mesh and the same work on ``"xla"`` on device 0,
which must agree bit for bit.

The numpy references run in a worker thread while the device works.  Times
and memory printed here are smoke readings, not benchmark numbers.  The last
line is one JSON object naming the device; any failed check exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# SNAP soc-LiveJournal1: nodes and edges
LJ_N, LJ_M = 4_847_571, 68_993_773
# chung_lu draws 1.3 m endpoint pairs and deduplicates few of them, so m/1.3
# is passed to land near LJ's edge count
CHUNG_LU_DRAWS = 1.3
# benchmarks/bench_backends.py small cell
BENCH_SMALL = (1200, 4800)
# the fraction of LJ that fits the smoke's 20-minute budget on one v5e
DEFAULT_SCALE = 0.05
BATCHES, BATCH_OPS = 4, 64
SHARD_BATCHES = 2
ALGORITHMS = ("semicore", "semicore+", "semicore*")


class SmokeFailure(Exception):
    pass


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def in_thread(fn):
    """Start ``fn`` on a worker thread; ``.result()`` joins and re-raises."""
    pool = ThreadPoolExecutor(max_workers=1)
    future = pool.submit(fn)
    pool.shutdown(wait=False)
    return future


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


def same_result(a, b) -> list:
    """Fields on which two DecompResults differ (pass-for-pass equality)."""

    bad = []
    if not np.array_equal(a.core, b.core):
        bad.append("core")
    if (a.cnt is None) != (b.cnt is None) or (
            a.cnt is not None and not np.array_equal(a.cnt, b.cnt)):
        bad.append("cnt")
    for f in ("iterations", "node_computations", "edge_block_reads",
              "node_table_reads", "updates_per_iter",
              "computations_per_iter"):
        if getattr(a, f) != getattr(b, f):
            bad.append(f)
    return bad


def probes_of(g) -> int:
    """h-index probes of a decompose of ``g`` (cores start at degrees)."""

    return max(1, math.ceil(math.log2(int(np.diff(g.indptr).max()) + 2)))


def lj_graph(scale: float, seed: int):
    from repro.graph import chung_lu

    n = max(2, round(LJ_N * scale))
    m = max(1, round(LJ_M * scale / CHUNG_LU_DRAWS))
    t0 = time.perf_counter()
    g = chung_lu(n, m, seed=seed)
    say(f"graph: chung_lu seed={seed} n={g.n} m={g.m} "
        f"directed_edges={len(g.adj)} dmax={int(np.diff(g.indptr).max())} "
        f"generate_s={time.perf_counter() - t0:.3f}")
    if scale != 1.0:
        say(f"reduced: scale={scale} of soc-LiveJournal1 "
            f"(n={LJ_N}, m={LJ_M}) -> n={g.n}, m={g.m}")
    return g


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_decompose(g, backend, clock, label, algorithm="semicore*"):
    from repro.core.semicore import decompose

    c0 = clock.total
    t0 = time.perf_counter()
    r = decompose(g, algorithm, "batch", backend=backend)
    wall = time.perf_counter() - t0
    say(f"smoke reading: {label} decompose {algorithm} wall_s={wall:.3f} "
        f"compile_s={clock.total - c0:.3f} passes={r.iterations}")
    return r


# ------------------------------------------------------------ one chip
def phase_xla_decompose(g, clock, dev):
    from repro.core.semicore import decompose

    ref = in_thread(lambda: decompose(g, "semicore*", "batch",
                                     backend="numpy"))
    rx = device_decompose(g, "xla", clock, "xla")
    say(f"smoke reading: xla peak_bytes_in_use={peak_bytes(dev)}")
    rn = ref.result()
    bad = same_result(rx, rn)
    check(not bad, f"xla decompose differs from numpy in {bad}")
    say(f"xla decompose: exact agreement with numpy (core, cnt, "
        f"{rn.iterations} passes, edge_block_reads={rn.edge_block_reads}, "
        f"node_table_reads={rn.node_table_reads}); degeneracy "
        f"{int(rn.core.max())}")
    return rx


def batches_of(g, seed, count):
    from repro.stream import admit_batch, sampled_stream

    ops = sampled_stream(g, count * BATCH_OPS, seed=seed)
    raw = [ops[i * BATCH_OPS:(i + 1) * BATCH_OPS] for i in range(count)]
    return raw, [admit_batch(b, n=g.n).batch for b in raw]


def run_writer(g, backend, state, raw, wal_dir):
    """Ingest ``raw`` batches through a CoreWriter; per epoch: core, cnt and
    the answers to the three queries."""
    from repro.stream import CoreWriter

    w = CoreWriter(g, backend=backend, state=state, wal_fsync=True,
                   wal_path=os.path.join(wal_dir, f"{backend}.wal"))
    out = []
    try:
        for ops in raw:
            t0 = time.perf_counter()
            st = w.ingest(ops)
            wall = time.perf_counter() - t0
            touched = np.unique([v for _, a, b in ops for v in (a, b)])
            out.append(dict(
                core=w.maintainer.core.copy(), cnt=w.maintainer.cnt.copy(),
                coreness=np.asarray(w.coreness(touched)), touched=touched,
                top_k=np.asarray(w.top_k(10)), degeneracy=int(w.degeneracy()),
                epoch=st.epoch, wall=wall, passes=st.iterations,
                applied=st.num_applied_deletes + st.num_applied_inserts))
        return out, w
    finally:
        w.close()


def phase_xla_maintenance(g, rx, seed, wal_dir):
    from repro.core.maintenance import CoreMaintainer
    from repro.stream.service import EpochView

    raw, admitted = batches_of(g, seed, BATCHES)

    def reference():
        m = CoreMaintainer(g, state=(rx.core, rx.cnt), backend="numpy")
        epochs = []
        for b in admitted:
            m.apply(b)
            epochs.append((m.core.copy(), m.cnt.copy()))
        return epochs

    ref = in_thread(reference)
    got, _ = run_writer(g, "xla", (rx.core, rx.cnt), raw, wal_dir)
    want = ref.result()
    for e, (gw, (core, cnt)) in enumerate(zip(got, want), 1):
        view = EpochView(e, core, np.zeros_like(core))
        check(np.array_equal(gw["core"], core), f"epoch {e}: core differs")
        check(np.array_equal(gw["cnt"], cnt), f"epoch {e}: cnt differs")
        check(np.array_equal(gw["coreness"], core[gw["touched"]]),
              f"epoch {e}: coreness query differs")
        check(np.array_equal(gw["top_k"], view.top_k(10)),
              f"epoch {e}: top_k query differs")
        check(gw["degeneracy"] == view.degeneracy(),
              f"epoch {e}: degeneracy query differs")
        say(f"xla maintenance epoch {gw['epoch']}: {gw['applied']} ops "
            f"applied, {gw['passes']} settle passes, core and cnt equal "
            f"numpy CoreMaintainer; queries agree (degeneracy "
            f"{gw['degeneracy']}); smoke reading: ingest_s={gw['wall']:.3f}")


def pallas_graphs(seed):
    """The bench small cell, and the largest LJ-density chung_lu graph whose
    semicore decompose the fused kernel's memory guard admits."""
    from repro.graph import chung_lu
    from repro.kernels import fused_superstep as fsk

    density = LJ_M / LJ_N / CHUNG_LU_DRAWS

    def build(n):
        return chung_lu(n, max(1, round(n * density)), seed=seed)

    def admitted(g):
        ft_dims = fsk.build_fused_table(g.indptr, g.adj, g.n,
                                        fsk.fused_block_edges()).dims
        try:
            fsk.check_memory(ft_dims, probes_of(g))
        except ValueError:
            return False
        return True

    small = chung_lu(*BENCH_SMALL, seed=seed)
    lo, hi = BENCH_SMALL[0], None
    check(admitted(small) and admitted(build(lo)),
          f"the fused kernel's memory guard refuses n={lo}")
    while hi is None:
        if admitted(build(2 * lo)):
            lo *= 2
        else:
            hi = 2 * lo
    while hi - lo > lo // 32:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if admitted(build(mid)) else (lo, mid)
    big = build(lo)
    say(f"pallas: the kernel's memory guard admits chung_lu n={big.n} "
        f"m={big.m} (LJ density), refuses n={hi}")
    return [("bench-small", small), ("guard-max", big)]


def phase_pallas(seed, clock):
    import jax
    import jax.numpy as jnp
    from repro.core import resident
    from repro.core.semicore import decompose
    from repro.kernels import fused_superstep as fsk
    from repro.kernels import resolve_interpret

    check(resolve_interpret(None) is False,
          "pallas would run in interpret mode on this device")
    for label, g in pallas_graphs(seed):
        for alg in ALGORITHMS:
            rn = decompose(g, alg, "batch", backend="numpy")
            rp = device_decompose(g, "pallas", clock, f"pallas {label}",
                                  alg)
            bad = same_result(rp, rn)
            check(not bad, f"pallas {label} {alg} differs from numpy in {bad}")
            say(f"pallas {label} {alg}: exact agreement with numpy "
                f"({rn.iterations} passes, n={g.n}, m={g.m}, "
                f"kernel_blocks_active={rp.kernel_blocks_active})")
        # the resident semicore* chunk at these shapes, compiled: the fused
        # kernel must be a Mosaic custom call, not an emulation
        ft = fsk.build_fused_table(g.indptr, g.adj, g.n,
                                   fsk.fused_block_edges())
        fn = resident._chunk_fns("pallas", 512, False, "semicore*", True)
        vec = jax.ShapeDtypeStruct((g.n,), jnp.int32)
        hlo = fn.lower(
            vec, vec, jax.ShapeDtypeStruct((g.n,), jnp.bool_),
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         ft.arrays),
            num_probes=probes_of(g), num_segments=g.n, chunk=resident.chunk_len(),
            dims=ft.dims).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"pallas {label}: compiled chunk has no tpu_custom_call")
        say(f"pallas {label}: compiled semicore* chunk holds the Mosaic "
            f"kernel (tpu_custom_call); dims={ft.dims}")


# ------------------------------------------------------------ four chips
def phase_shard(g, clock, wal_dir, seed):
    import jax
    from repro.core.engine import ShardedBackend

    devs = jax.devices()
    check(len(devs) >= 4, f"--shard needs 4 devices, found {len(devs)}")
    sb = ShardedBackend(num_shards=4)
    sb.retain_structure = True
    rs_ = device_decompose(g, sb, clock, "shard x4")
    mesh_devs = list(sb._resident.mesh.devices.flat)
    ids = {d.id for d in mesh_devs}
    check(len(ids) == 4 and all(d.platform == "tpu" for d in mesh_devs),
          f"shard mesh spans {mesh_devs}, not 4 distinct TPUs")
    say(f"shard mesh: {len(ids)} distinct TPU devices {sorted(ids)}")
    sb.retain_structure = False
    sb.release_resident()
    rx = device_decompose(g, "xla", clock, "xla device 0")
    bad = same_result(rs_, rx)
    check(not bad, f"shard decompose differs from xla in {bad}")
    say(f"shard decompose: bit-identical to xla ({rx.iterations} passes, "
        f"degeneracy {int(rx.core.max())})")

    raw, _ = batches_of(g, seed, SHARD_BATCHES)
    got_s, w = run_writer(g, "shard", (rx.core, rx.cnt), raw, wal_dir)
    wdevs = {d.id for d in w.maintainer.backend._resident.mesh.devices.flat}
    check(len(wdevs) == 4, f"shard writer mesh spans {sorted(wdevs)}")
    got_x, _ = run_writer(g, "xla", (rx.core, rx.cnt), raw, wal_dir)
    for s, x in zip(got_s, got_x):
        for k in ("core", "cnt", "coreness", "top_k"):
            check(np.array_equal(s[k], x[k]),
                  f"shard epoch {s['epoch']}: {k} differs from xla")
        check(s["degeneracy"] == x["degeneracy"],
              f"shard epoch {s['epoch']}: degeneracy differs from xla")
        say(f"shard maintenance epoch {s['epoch']}: {s['applied']} ops, "
            f"core, cnt and queries bit-identical to xla; smoke reading: "
            f"ingest_s shard={s['wall']:.3f} xla={x['wall']:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=DEFAULT_SCALE,
                    help="fraction of soc-LiveJournal1's n and m")
    ap.add_argument("--shard", action="store_true",
                    help="run only the 4-chip sharded path and its xla "
                         "comparison")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        say(f"FAIL: no repro package under {src}; run from a checkout")
        return 1
    sys.path.insert(0, src)
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        say(f"FAIL: no TPU found; JAX's first device is {dev.platform!r}")
        return 1
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")

    from repro.runtime import enable_compile_cache

    say(f"compile cache: {enable_compile_cache(ROOT)}")
    clock = CompileClock()
    wal_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        g = lj_graph(args.scale, args.seed)
        if args.shard:
            phase_shard(g, clock, wal_dir, args.seed)
        else:
            rx = phase_xla_decompose(g, clock, dev)
            phase_xla_maintenance(g, rx, args.seed, wal_dir)
            del g, rx
            phase_pallas(args.seed, clock)
    except SmokeFailure as e:
        say(f"FAIL: {e}")
        return 1
    finally:
        shutil.rmtree(wal_dir, ignore_errors=True)
    say(f"smoke reading: total_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.total:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
