"""Benchmark entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per the repo contract) plus a
claims-check section validating the paper's Fig. 2/3 statements against this
run.  ``--full`` uses paper-scale repeats/iterations (slow on 1 CPU);
the default is a moderate configuration sized for this container.
"""
from __future__ import annotations

import argparse


def emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-fig2", action="store_true")
    args = ap.parse_args()

    # Figs. 2/3 now run through the unified Mango-vs-TPE harness
    # (benchmarks/paper_figures.py) — claims logic lives there only.
    from benchmarks.paper_figures import run_figures
    grid = "full" if args.full else "default"
    figs = ["fig3"] if args.skip_fig2 else ["fig3", "fig2"]
    run_figures(figs, grid=grid)

    print("# === Batch-size scaling (hallucination strategy) ===")
    from benchmarks import batch_scaling
    for batch, iters, evals in batch_scaling.run(
            repeats=3 if not args.full else 5):
        emit(f"batch_scaling_b{batch}", iters * 1e6,
             f"iters_to_target={iters:.1f};evals={evals:.1f}")

    print("# === Roofline (from dry-run artifacts) ===")
    from benchmarks import roofline
    for name, us, derived in roofline.csv_rows("baseline"):
        emit(name, us, derived)


if __name__ == "__main__":
    main()
