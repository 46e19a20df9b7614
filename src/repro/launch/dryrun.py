import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, and extract the roofline terms from the compiled artifact.

  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch yi-34b
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi            # all

Outputs one JSON per cell under benchmarks/results/dryrun/, consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""
import argparse
import json
import re
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp

from .mesh import make_production_mesh
from .steps import build_step
from ..configs import get_config, shape_names, ARCH_IDS

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/results/dryrun")

# v5e hardware constants (DESIGN/EXPERIMENTS roofline)
PEAK_FLOPS = 197e12         # bf16 / chip
HBM_BW = 819e9              # B/s / chip
ICI_BW = 50e9               # B/s effective per-chip ICI (per link figure)

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(.*?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip ICI traffic estimate per collective family, from HLO text.

    Conventions: all-reduce ~ 2x result bytes (ring); all-gather /
    all-to-all / collective-permute ~ result bytes; reduce-scatter ~
    result bytes x (group-1) (operand-sized ring pass).
    """
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        rb = _shape_bytes(m.group(1))
        op = m.group(2)
        if op == "all-reduce":
            traffic = 2 * rb
        elif op == "reduce-scatter":
            g = 2
            gm = _GROUPS_RE.search(line)
            if gm:
                g = len(gm.group(1).split(","))
            traffic = rb * max(g - 1, 1)
        else:
            traffic = rb
        out[op] += traffic
    out["total"] = sum(out.values())
    return out


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def _compile_bundle(bundle, mesh):
    if bundle.in_shardings is not None:
        fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)
    else:
        fn = bundle.fn  # already jit-wrapped (coregraph engine)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*bundle.args)
        compiled = lowered.compile()
    return compiled


def _metrics(compiled) -> dict:
    cost = _cost_dict(compiled)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes(compiled.as_text()),
    }


def _shard_frac(sharding) -> int:
    """How many ways a NamedSharding splits its array."""
    f = 1
    spec = sharding.spec
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        for nm in names:
            f *= dict(sharding.mesh.shape)[nm]
    return f


def _args_bytes_per_chip(bundle) -> float:
    """Per-chip bytes of all step arguments (params, opt state, caches, batch)."""
    total = 0.0
    if bundle.in_shardings is None:
        return 0.0
    for aval_tree, sh_tree in zip(bundle.args, bundle.in_shardings):
        avals = jax.tree.leaves(aval_tree)
        shs = jax.tree.leaves(
            sh_tree, is_leaf=lambda x: hasattr(x, "spec"))
        for a, s in zip(avals, shs):
            total += np.prod(a.shape) * jnp.dtype(a.dtype).itemsize / _shard_frac(s)
    return total


def _memory_model(arch, shape, mesh, bundle, chips) -> dict:
    """Analytic per-chip HBM model (the TPU 'does it fit' check; the CPU
    backend's temp_bytes lacks TPU fusion/remat and wildly overstates)."""
    from ..configs import get_config

    args = _args_bytes_per_chip(bundle)
    cfg = get_config(arch)
    act = 0.0
    grads = 0.0
    if cfg.kind == "coregraph":
        # replicated node state (core in + combined out) + per-chip edge
        # shard (dst/mask) + per-chip owned-slot state (ids/mask/
        # lsegptr/cnt/active); the scatter id map is gathered on-mesh per
        # chunk, not shipped replicated (resident._shard_chunk_fn)
        args = 2 * cfg.n * 4 + cfg.m_directed / chips * 5 \
            + cfg.n / chips * 14
        act = cfg.m_directed / chips * 8  # gathered nbr cores + index arrays
    elif bundle.name == "train_step" and cfg.kind == "lm":
        accum = bundle.static.get("accum", 1)
        from ..configs import SHAPES_BY_KIND
        sh = SHAPES_BY_KIND["lm"][shape]
        ba_shards = chips // dict(mesh.shape).get("model", 1)
        tok_chip = sh["global_batch"] * sh["seq_len"] / ba_shards / accum
        # remat saves one (tokens, d_model) bf16 per layer + ~8x working set
        act = tok_chip * cfg.d_model * 2 * (cfg.n_layers + 8)
        grads = bundle.num_params * 4 / chips  # fp32 grad accum, fully sharded
    elif bundle.name == "train_step":
        act = args * 4  # GNN/recsys: a few activation-sized buffers
        grads = bundle.num_params * 4  # replicated small models
    else:
        act = args * 0.25
    total = args + act + grads
    return {
        "args_bytes_per_chip": args,
        "activation_bytes_per_chip": act,
        "grad_bytes_per_chip": grads,
        "total_bytes_per_chip": total,
        "fits_16GB_hbm": bool(total < 16e9 * 0.92),
    }


def run_cell(arch: str, shape: str, mesh, mesh_name: str, chips: int) -> dict:
    from ..configs import get_config

    t0 = time.time()
    bundle = build_step(arch, shape, mesh)
    compiled = _compile_bundle(bundle, mesh)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
    }

    # --- roofline metrics -------------------------------------------------
    # HloCostAnalysis counts a `while` body once, so scanned layer stacks
    # undercount by ~L.  For LM cells we therefore compile two *unrolled*
    # shallow variants (depth d0, d0+1) and extrapolate linearly in depth;
    # other families have no layer scans (python loops) and are exact.
    cfg = get_config(arch)
    extrapolated = False
    if cfg.kind == "lm":
        kd = cfg.moe.first_k_dense if cfg.moe is not None else 0
        d0 = kd + 1
        os.environ["REPRO_UNROLL_SCANS"] = "1"
        # grad accumulation is metric-neutral (same total flops/bytes/
        # collectives); accum=1 keeps the unrolled metric HLO small
        os.environ["REPRO_ACCUM_TOKENS"] = str(10**9)
        try:
            b0 = build_step(arch, shape, mesh, depth_override=d0)
            m0 = _metrics(_compile_bundle(b0, mesh))
            b1 = build_step(arch, shape, mesh, depth_override=d0 + 1)
            m1 = _metrics(_compile_bundle(b1, mesh))
        finally:
            del os.environ["REPRO_UNROLL_SCANS"]
            del os.environ["REPRO_ACCUM_TOKENS"]
        L = cfg.n_layers

        def extrap(a, b):
            # linear in depth; if the partitioner's strategy flips between
            # depths (negative delta), fall back to the mean per-layer rate
            delta = b - a
            if delta <= 0:
                delta = b / (d0 + 1)
            return a + (L - d0) * delta

        flops = extrap(m0["flops"], m1["flops"])
        bytes_accessed = extrap(m0["bytes"], m1["bytes"])
        coll = {k: extrap(m0["coll"][k], m1["coll"][k]) for k in m0["coll"]}
        extrapolated = True
    elif arch.startswith("semicore"):
        # per-superstep terms: unroll the probe loop, body counted once
        os.environ["REPRO_UNROLL_SCANS"] = "1"
        try:
            m0 = _metrics(_compile_bundle(build_step(arch, shape, mesh), mesh))
        finally:
            del os.environ["REPRO_UNROLL_SCANS"]
        flops, bytes_accessed, coll = m0["flops"], m0["bytes"], m0["coll"]
    else:
        m0 = _metrics(compiled)
        flops, bytes_accessed, coll = m0["flops"], m0["bytes"], m0["coll"]

    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    collective_s = coll["total"] / ICI_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)], key=lambda kv: kv[1])[0]

    return {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
        "step": bundle.name, "num_params": bundle.num_params,
        "ok": True, "extrapolated_depth_metrics": extrapolated,
        "compile_s": round(t_compile, 2),
        "memory": mem_d,
        "memory_model": _memory_model(arch, shape, mesh, bundle, chips),
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collective_bytes_per_chip": coll,
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dominant,
        },
    }


def all_cells():
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in shape_names(cfg):
            cells.append((arch, shape))
    # the paper's own workload (extra beyond the 40 assigned cells)
    cells.append(("semicore-webscale", "decompose"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False), 256))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True), 512))

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]

    failures = 0
    for mesh_name, mesh, chips in meshes:
        for arch, shape in cells:
            tag = f"{arch}__{shape}__{mesh_name}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag}")
                continue
            print(f"[run ] {tag}", flush=True)
            try:
                rec = run_cell(arch, shape, mesh, mesh_name, chips)
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"[FAIL] {tag}: {rec['error']}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec.get("ok"):
                r = rec["roofline"]
                print(f"[ ok ] {tag} compile={rec['compile_s']:.1f}s "
                      f"flops/chip={rec['hlo_flops_per_chip']:.3g} "
                      f"dom={r['dominant']}", flush=True)
    print(f"done; failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
