"""Unified Mango-vs-TPE convergence harness (paper Figs. 2 and 3).

One entry point for the paper's two evaluation figures, both running
through the same ask/tell core (``run_algorithms`` -> ``Tuner``): Fig. 2 is
the GBM-on-wine classifier tuning task (maximize CV accuracy), Fig. 3 the
modified mixed-variable Branin (minimize).  Each figure's paper claims are
checked against the run and emitted as ``# CLAIM`` lines; ``--json`` writes
the per-algorithm best-so-far traces plus the claim verdicts so the CI
``figures`` job can archive the convergence trajectory per commit
(``BENCH_paper_figures.json``), the same pattern as the proposal-latency
bench.

``--quick`` selects a grid sized for CI (a few minutes on one CPU);
the default grid matches ``benchmarks/run.py``'s moderate configuration and
``--full`` the paper-scale one.
"""
from __future__ import annotations

import argparse
import json
import time


def run_fig2(n_iters=15, repeats=3, parallel_batch=5):
    from benchmarks import fig2_classifier
    return fig2_classifier.run(n_iters=n_iters, repeats=repeats,
                               parallel_batch=parallel_batch)


def run_fig3(n_iters=15, repeats=5, parallel_batch=5):
    from benchmarks import fig3_branin
    return fig3_branin.run(n_iters=n_iters, repeats=repeats,
                           parallel_batch=parallel_batch)


def _final(traces, name):
    return float(traces[name][:, -1].mean())


def claims_fig2(tr):
    """The paper's Fig. 2 statements -> [(claim, detail, passed)]."""
    ms, ts = _final(tr, "mango-serial"), _final(tr, "tpe-serial")
    mp = _final(tr, "mango-parallel")
    mc = _final(tr, "mango-clustering")
    tp = _final(tr, "tpe-parallel")
    rnd = _final(tr, "random-parallel")
    bo_min = min(ms, mp, mc, tp)
    return [
        ("fig2 'all BO >= random (within noise)'",
         f"min(BO)={bo_min:.4f} vs random={rnd:.4f}", bo_min >= rnd - 0.01),
        ("fig2 'Mango serial slightly better than Hyperopt serial'",
         f"{ms:.4f} vs {ts:.4f}", ms >= ts - 0.005),
        ("fig2 'Mango parallel >= Hyperopt parallel (<=40 iters)'",
         f"{max(mp, mc):.4f} vs {tp:.4f}", max(mp, mc) >= tp - 0.005),
    ]


def claims_fig3(tr):
    """The paper's Fig. 3 statements (minimization: lower is better)."""
    ms, ts = _final(tr, "mango-serial"), _final(tr, "tpe-serial")
    mp, tp = _final(tr, "mango-parallel"), _final(tr, "tpe-parallel")
    rs = _final(tr, "random-serial")
    return [
        ("fig3 'Mango outperforms Hyperopt in serial'",
         f"{ms:.3f} <= {ts:.3f}", ms <= ts + 0.05),
        ("fig3 'Mango outperforms Hyperopt in parallel'",
         f"{mp:.3f} <= {tp:.3f}", mp <= tp + 0.05),
        ("fig3 'BO beats random'", f"{ms:.3f} <= {rs:.3f}",
         ms <= rs + 1e-9),
    ]


FIGURES = {
    # name -> (runner, claims, emit-prefix, derived-key)
    "fig2": (run_fig2, claims_fig2, "fig2_wine", "best_acc"),
    "fig3": (run_fig3, claims_fig3, "fig3_branin", "best_final"),
}

# (n_iters, repeats, parallel_batch) per figure and grid size
GRIDS = {
    "quick": {"fig2": (6, 2, 3), "fig3": (10, 3, 5)},
    "default": {"fig2": (15, 3, 5), "fig3": (15, 5, 5)},
    "full": {"fig2": (40, 10, 5), "fig3": (30, 10, 5)},
}


def run_figures(figs, grid="default", json_path=None):
    """Run the selected figures, print CSV rows + claim lines, and return
    the JSON-able result document."""
    doc = {"benchmark": "paper_figures", "grid": grid, "figures": {}}
    for fig in figs:
        runner, claims_fn, prefix, key = FIGURES[fig]
        n_iters, repeats, pb = GRIDS[grid][fig]
        print(f"# === {fig}: n_iters={n_iters} repeats={repeats} "
              f"batch={pb} ===")
        t0 = time.time()
        traces = runner(n_iters=n_iters, repeats=repeats, parallel_batch=pb)
        wall = time.time() - t0
        algos = {}
        for name, trace in traces.items():
            final = float(trace[:, -1].mean())
            # per-algorithm per-repeat wall share: same us_per_call metric
            # the old run.py emitted, so the CSV trajectory stays
            # comparable across commits
            us = wall / max(len(traces), 1) * 1e6 / max(repeats, 1)
            print(f"{prefix}_{name},{us:.1f},{key}={final:.4f}", flush=True)
            algos[name] = {"final_mean": final,
                           "trace_mean": trace.mean(axis=0).tolist()}
        claims = []
        for claim, detail, passed in claims_fn(traces):
            print(f"# CLAIM {claim}: {detail} -> "
                  f"{'PASS' if passed else 'FAIL'}")
            claims.append({"claim": claim, "detail": detail,
                           "passed": bool(passed)})
        doc["figures"][fig] = {"n_iters": n_iters, "repeats": repeats,
                               "parallel_batch": pb, "wall_s": round(wall, 1),
                               "algos": algos, "claims": claims}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {json_path}")
    return doc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fig", choices=["2", "3", "all"], default="all")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized grid (a few minutes on one CPU)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale repeats/iterations (slow on 1 CPU)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write traces + claim verdicts as JSON")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    grid = "quick" if args.quick else ("full" if args.full else "default")
    figs = ["fig2", "fig3"] if args.fig == "all" else [f"fig{args.fig}"]
    run_figures(figs, grid=grid, json_path=args.json)


if __name__ == "__main__":
    main()
