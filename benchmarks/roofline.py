"""§Roofline: assemble the per-cell roofline table from dry-run artifacts.

Terms (per chip, seconds):
  compute    = HLO_FLOPs / peak_FLOPs          (197 TF/s bf16, v5e)
  memory     = HLO_bytes / HBM_bw              (819 GB/s)
  collective = collective_bytes / link_bw      (~50 GB/s ICI)

plus MODEL_FLOPS = 6·N·D (dense train) / 6·N_active·D (MoE) / 2·N·D (serve)
and the useful-compute ratio MODEL_FLOPS / (chips x HLO_FLOPs).

``--superstep`` switches to the decomposition engine's roofline: achieved
bytes/s of the fused superstep — numerator sourced *entirely* from the
telemetry registry (``repro_io_bytes_read_total`` delta around one warm
decompose, no hand math) — against a peak measured by a same-process memcpy
probe.  The superstep is memory-bound by construction (one h-index probe per
touched edge), so achieved/peak is the headroom number.
"""
from __future__ import annotations

import glob
import json
import os
import time

import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")
SUPERSTEP_RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _lm_model_flops(arch: str, shape: str) -> float:
    from repro.configs import get_config, SHAPES_BY_KIND
    from repro.models.transformer import lm_param_specs, layer_groups
    from repro.models.params import tree_num_params

    cfg = get_config(arch)
    specs = lm_param_specs(cfg)
    total = tree_num_params(specs)
    n_active = total
    if cfg.moe is not None:
        m = cfg.moe
        L_moe = cfg.n_layers - m.first_k_dense
        routed = L_moe * m.num_experts * 3 * cfg.d_model * m.d_ff_expert
        n_active = total - routed * (1 - m.top_k / m.num_experts)
    sh = SHAPES_BY_KIND["lm"][shape]
    if sh["step"] == "train":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 6.0 * n_active * tokens
    if sh["step"] == "prefill":
        return 2.0 * n_active * sh["global_batch"] * sh["seq_len"]
    return 2.0 * n_active * sh["global_batch"]  # decode: one token / request


def _gnn_model_flops(arch: str, shape: str) -> float:
    from repro.configs import get_config, SHAPES_BY_KIND

    cfg = get_config(arch)
    sh = SHAPES_BY_KIND["gnn"][shape]
    d = cfg.d_hidden
    if sh["mode"] == "full":
        E, N, F = sh["n_edges"], sh["n_nodes"], sh["d_feat"]
    elif sh["mode"] == "sampled":
        B = sh["batch_nodes"]
        f1, f2 = sh["fanout"]
        N = B * (1 + f1 + f1 * f2)
        E = 2 * (B * f1 + B * f1 * f2)
        F = sh["d_feat"]
    else:
        N = sh["batch"] * sh["n_nodes"]
        E = 2 * sh["batch"] * sh["n_edges"]
        F = sh["d_feat"]
    fwd = cfg.n_layers * (2 * E * d + 2 * N * d * max(F, d))
    return 3.0 * fwd  # train ~ 3x forward


def _recsys_model_flops(shape: str) -> float:
    from repro.configs import get_config, SHAPES_BY_KIND

    cfg = get_config("mind")
    sh = SHAPES_BY_KIND["recsys"][shape]
    B = sh["batch"]
    D, K, L = cfg.embed_dim, cfg.n_interests, cfg.hist_len
    routing = cfg.capsule_iters * 2 * B * K * L * D * 2
    mlp = 2 * B * K * (2 * D * cfg.mlp_dim + cfg.mlp_dim * D)
    f = routing + mlp + 2 * B * L * D * D
    if sh["step"] == "train":
        f = 3 * f + 2 * B * cfg.num_sampled_negatives * D * 3
    if sh["step"] == "retrieval":
        f += 2 * sh["n_candidates"] * K * D
    return float(f)


def model_flops(arch: str, shape: str) -> float:
    if arch.startswith("semicore"):
        return 0.0
    from repro.configs import get_config

    kind = get_config(arch).kind
    if kind == "lm":
        return _lm_model_flops(arch, shape)
    if kind == "gnn":
        return _gnn_model_flops(arch, shape)
    return _recsys_model_flops(shape)


def load_table(mesh: str = "single_pod_16x16") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(RESULTS, f"*__{mesh}.json"))):
        r = json.load(open(path))
        if not r.get("ok"):
            rows.append({"arch": r["arch"], "shape": r["shape"], "ok": False})
            continue
        mf = model_flops(r["arch"], r["shape"])
        hlo_total = r["hlo_flops_per_chip"] * r["chips"]
        roof = r["roofline"]
        bound_s = max(roof["compute_s"], roof["memory_s"], roof["collective_s"])
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "ok": True,
            "chips": r["chips"], "step": r["step"],
            "compute_s": roof["compute_s"], "memory_s": roof["memory_s"],
            "collective_s": roof["collective_s"], "dominant": roof["dominant"],
            "model_flops": mf,
            "useful_ratio": (mf / hlo_total) if hlo_total else 0.0,
            "roofline_fraction": (roof["compute_s"] / bound_s) if bound_s else 0.0,
            "mfu_bound": (mf / r["chips"] / 197e12) / bound_s if bound_s else 0.0,
            "hbm_bytes_per_chip": r["memory"]["argument_bytes"]
            + r["memory"]["temp_bytes"],
        })
    return rows


def print_table(mesh: str = "single_pod_16x16"):
    rows = load_table(mesh)
    hdr = (f"{'arch':<18} {'shape':<14} {'dom':<10} {'compute_s':>10} "
           f"{'memory_s':>10} {'collect_s':>10} {'useful%':>8} {'MFUbound%':>9}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if not r.get("ok"):
            print(f"{r['arch']:<18} {r['shape']:<14} FAILED")
            continue
        print(f"{r['arch']:<18} {r['shape']:<14} {r['dominant']:<10} "
              f"{r['compute_s']:>10.3e} {r['memory_s']:>10.3e} "
              f"{r['collective_s']:>10.3e} {100 * r['useful_ratio']:>7.1f}% "
              f"{100 * r['mfu_bound']:>8.1f}%")
    return rows


# ====================================================== superstep roofline
def measured_memcpy_peak(nbytes: int = 1 << 27, repeats: int = 5) -> float:
    """Achievable host copy bandwidth in bytes/s (read + write counted).

    The paper's blocked I/O model charges the superstep for bytes *read*;
    the honest peak for that charge on a host runner is a large memcpy —
    the same streams the fused pass moves, with none of its arithmetic.
    """
    src = np.empty(nbytes // 8, dtype=np.float64)
    src.fill(1.0)
    dst = np.empty_like(src)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    return 2.0 * nbytes / best  # bytes touched = read + write


def superstep_roofline(quick: bool = False,
                       backends=("numpy", "xla")) -> list[dict]:
    """Achieved-vs-peak bytes/s of the fused superstep, registry-sourced."""
    from repro.core.semicore import decompose
    from repro.graph import chung_lu
    from repro.kernels import fused_superstep as fsk
    from repro.obs import metrics as obs_metrics

    n, m, block_edges = (3_000, 13_000, 512) if quick \
        else (25_000, 110_000, 4096)
    g = chung_lu(n, m, seed=8)
    peak = measured_memcpy_peak(1 << 24 if quick else 1 << 27)
    rows = []
    for backend in backends:
        decompose(g, "semicore*", "batch", block_edges=block_edges,
                  backend=backend)  # warm jit caches out of the measurement
        snap = obs_metrics.get_registry().snapshot()
        t0 = time.perf_counter()
        r = decompose(g, "semicore*", "batch", block_edges=block_edges,
                      backend=backend)
        wall = time.perf_counter() - t0
        delta = obs_metrics.get_registry().delta(snap)
        nbytes = obs_metrics.sum_by_name(delta, "repro_io_bytes_read_total")
        achieved = nbytes / max(wall, 1e-9)
        rows.append({
            "backend": backend,
            "algorithm": "semicore*",
            "graph": {"n": g.n, "m": g.m, "block_edges": block_edges},
            "fused_kernel": backend == "pallas" and fsk.fused_enabled(),
            "wall_seconds": round(wall, 5),
            "bytes_read": int(nbytes),
            "passes": int(obs_metrics.sum_by_name(
                delta, "repro_engine_passes_total")),
            "achieved_bytes_per_s": achieved,
            "peak_bytes_per_s": peak,
            "roofline_fraction": achieved / peak,
            "iterations_check": r.iterations,
        })
    return rows


def print_superstep(quick: bool = False, fused: bool = False) -> list[dict]:
    """``fused`` adds the pallas backend (the one-pallas_call-per-pass
    superstep, DESIGN.md §16) to the sweep and writes a separate JSON so
    the two modes don't clobber each other in CI."""
    backends = ("numpy", "xla", "pallas") if fused else ("numpy", "xla")
    rows = superstep_roofline(quick, backends=backends)
    hdr = (f"{'backend':<8} {'wall_s':>9} {'bytes_read':>12} "
           f"{'achieved GB/s':>14} {'peak GB/s':>10} {'roofline%':>10}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['backend']:<8} {r['wall_seconds']:>9.4f} "
              f"{r['bytes_read']:>12,} "
              f"{r['achieved_bytes_per_s'] / 1e9:>14.3f} "
              f"{r['peak_bytes_per_s'] / 1e9:>10.3f} "
              f"{100 * r['roofline_fraction']:>9.1f}%")
    os.makedirs(SUPERSTEP_RESULTS, exist_ok=True)
    name = "fused_superstep_roofline.json" if fused \
        else "superstep_roofline.json"
    path = os.path.join(SUPERSTEP_RESULTS, name)
    with open(path, "w") as f:
        json.dump({"rows": rows}, f, indent=2)
        f.write("\n")
    if fused:
        # markdown mirror for $GITHUB_STEP_SUMMARY (scripts/ci.sh)
        md = os.path.join(SUPERSTEP_RESULTS, "fused_superstep_roofline.md")
        g = rows[0]["graph"]
        with open(md, "w") as f:
            f.write(f"### Fused-superstep roofline (semicore*, n={g['n']}, "
                    f"m={g['m']}, registry-sourced bytes)\n\n")
            f.write("| backend | fused kernel | warm wall | bytes read | "
                    "achieved GB/s | roofline |\n|---|---|---|---|---|---|\n")
            for r in rows:
                f.write(f"| {r['backend']} | "
                        f"{'yes' if r['fused_kernel'] else '-'} | "
                        f"{r['wall_seconds']:.3f}s | {r['bytes_read']:,} | "
                        f"{r['achieved_bytes_per_s'] / 1e9:.3f} | "
                        f"{100 * r['roofline_fraction']:.1f}% |\n")
    print(f"wrote {path}")
    return rows


if __name__ == "__main__":
    import argparse
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("mesh", nargs="?", default="single_pod_16x16")
    ap.add_argument("--superstep", action="store_true",
                    help="registry-sourced achieved-vs-peak bytes/s of the "
                    "fused superstep")
    ap.add_argument("--fused-superstep", action="store_true",
                    help="like --superstep but includes the pallas backend "
                    "(single-kernel fused superstep)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.superstep or args.fused_superstep:
        print_superstep(quick=args.quick, fused=args.fused_superstep)
    else:
        print_table(args.mesh)
