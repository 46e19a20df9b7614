"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes JSON artifacts under
benchmarks/results/.  Set REPRO_BENCH_FULL=1 for the full-size suite.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../src"))

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}")


def _save(name: str, obj):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, name + ".json"), "w") as f:
        json.dump(obj, f, indent=1, default=str)


def main() -> None:
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    full = os.environ.get("REPRO_BENCH_FULL") == "1"
    from benchmarks import paper_figs

    print("name,us_per_call,derived")

    # ---- Fig. 9: decomposition across datasets -----------------------------
    datasets = ("dblp-sim", "youtube-sim", "wiki-sim", "cpt-sim", "lj-sim",
                "orkut-sim") if full else ("dblp-sim", "youtube-sim", "cpt-sim")
    rows = paper_figs.bench_decomposition(datasets, run_emcore=True)
    _save("fig9_decomposition", rows)
    for r in rows:
        _emit(f"fig9/{r['dataset']}/semicore_star", r["semicore_star_s"] * 1e6,
              f"io={r['semicore_star_io_blocks']};iters={r['semicore_star_iters']};"
              f"mem={r['semicore_star_mem_bytes']}")
        _emit(f"fig9/{r['dataset']}/semicore", r["semicore_s"] * 1e6,
              f"io={r['semicore_io_blocks']}")
        _emit(f"fig9/{r['dataset']}/imcore", r["imcore_s"] * 1e6,
              f"mem={r['imcore_mem_bytes']}")
        if "emcore_s" in r:
            _emit(f"fig9/{r['dataset']}/emcore", r["emcore_s"] * 1e6,
                  f"io={r['emcore_io_blocks']};mem={r['emcore_mem_bytes']};"
                  f"overbudget={r['emcore_over_budget_rounds']}")

    # ---- Fig. 3: convergence profile ---------------------------------------
    conv = paper_figs.bench_convergence(("twitter-sim",) if not full
                                        else ("twitter-sim", "uk-sim"))
    _save("fig3_convergence", conv)
    for r in conv:
        _emit(f"fig3/{r['dataset']}", 0.0,
              f"iters={r['iterations']};first={r['first_iter_updates']};"
              f"late={r['late_iter_updates']}")

    # ---- Fig. 10: maintenance ----------------------------------------------
    maint = paper_figs.bench_maintenance(
        "lj-sim" if full else "dblp-sim", num_edges=100 if full else 40)
    _save("fig10_maintenance", maint)
    for k in ("delete_star", "semiinsert", "semiinsert_star"):
        _emit(f"fig10/{k}", maint[f"{k}_avg_s"] * 1e6,
              f"io={maint[f'{k}_avg_io']:.1f};"
              f"comp={maint[f'{k}_avg_computations']:.1f}")

    # ---- Fig. 11/12: scalability -------------------------------------------
    scal = paper_figs.bench_scalability(
        "twitter-sim" if full else "dblp-sim",
        fracs=(0.2, 0.6, 1.0) if not full else (0.2, 0.4, 0.6, 0.8, 1.0))
    _save("fig11_scalability", scal)
    for r in scal:
        _emit(f"fig11/{r['mode']}/{int(r['frac'] * 100)}pct",
              r["semicore_star_s"] * 1e6,
              f"n={r['n']};m={r['m']};basic_s={r['semicore_s']:.3f}")

    # ---- §Roofline tables (from dry-run artifacts, if present) -------------
    try:
        from benchmarks.roofline import load_table
        for mesh, name in [("single_pod_16x16", "roofline_single_pod"),
                           ("multi_pod_2x16x16", "roofline_multi_pod")]:
            table = load_table(mesh)
            if not table:
                continue
            _save(name, table)
            for t in table:
                if t.get("ok"):
                    _emit(f"roofline[{mesh}]/{t['arch']}/{t['shape']}", 0.0,
                          f"dom={t['dominant']};useful={t['useful_ratio']:.3f}")
    except Exception as e:  # dry-run not yet executed
        print(f"# roofline skipped: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
