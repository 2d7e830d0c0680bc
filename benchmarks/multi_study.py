"""Multi-study throughput: the vmap'd StudyBank ask vs a Python loop.

The tentpole claim (ISSUE 6): N concurrent studies cost ONE device
dispatch, not N.  Two arms per fleet size B:

  * ``multi_study_loop_{B}``: B independent ``AskTellOptimizer`` objects
    asked one after another — the pre-bank serving pattern.  Every study
    pays its own jit dispatch, candidate draw, and host round-trip.
  * ``studies_per_sec_{B}``: one ``StudyBank`` of B studies served by a
    single ``ask_all`` — one columnar candidate draw, one shape-bucketed
    gather, one vmap'd fused program.

Both arms run the same strategy, the same ``mc_samples``, and identically
pre-seeded studies (~20 observations, past the random phase).  The
default candidate budget is small (``n_mc=32``) because this row measures
*serving overhead amortization* — dispatch, gather, host round-trips —
which is what the bank actually batches away; both arms always get the
identical budget, and larger budgets shift both arms toward the same
elementwise-scoring floor.  The timed op is the steady-state ask: each
rep's proposals are told *failed* in the untimed setup slot, so
observation counts — and therefore every device shape and the fit
schedule — stay frozen across reps.  Rows are timed
interleaved (same convention as ``proposal_latency``) so CPU-share
throttling hits both arms equally; ``bench_delta`` normalizes the
``studies_per_sec`` rows against the same-run loop row, which is what the
CI gate (``studies_per_sec_256:1.25``) blocks on.  Acceptance target:
bank >= 50x the loop at B=256.

``steady_state_retrace``: the zero-retrace proof for the shape-bucket
schedule.  One bank grows 64 -> 1024 observations, asking at every bucket
edge (edge-1 / edge / edge+1) and at interior points; each staged jitted
bank entry point (``gp.BANK_JITS``: factors, prescales, dist, exp, pick,
absorb, fit) should compile exactly once per power-of-2 bucket it is
dispatched at and never again.  The row's value is ``new_cache_entries -
expected_compiles`` summed over entry points — nonzero means a retrace
leaked into the steady state, and the script exits 1 (the CI bench job
fails).

``--json PATH`` writes the rows for the CI perf-trajectory archive.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

ROWS = []   # every emitted row, for --json


def _emit(name, us, derived):
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


def _interleaved_medians(calls, reps=3, setups=None):
    """Median seconds per call, calls interleaved within each rep (see
    ``proposal_latency._interleaved_medians`` — same throttle-resistant
    convention).  ``setups[i]`` runs untimed before each timed call."""
    samples = [[] for _ in calls]
    for i, c in enumerate(calls):        # warmup: compile the timed path
        if setups is not None and setups[i] is not None:
            setups[i]()
        c()
    for _ in range(reps):
        for i, c in enumerate(calls):
            if setups is not None and setups[i] is not None:
                setups[i]()
            t0 = time.perf_counter()
            c()
            samples[i].append(time.perf_counter() - t0)
    return [float(np.median(s)) for s in samples]


def _space():
    from scipy import stats
    return {"x": stats.uniform(0, 1), "y": stats.uniform(-1, 2),
            "z": stats.uniform(0, 3)}


def _seed_study(opt, k, rng):
    for _ in range(k):
        p = {"x": float(rng.uniform(0, 1)), "y": float(rng.uniform(-1, 1)),
             "z": float(rng.uniform(0, 3))}
        opt.observe_params(p, float(rng.normal()))


def run_throughput(fleet_sizes=(16, 64, 256), n_obs=20, n_mc=32, reps=3,
                   seed=0):
    """studies/sec, bank vs loop, across fleet size."""
    from repro.core import AskTellOptimizer, StudyBank

    results = []
    for B in fleet_sizes:
        rng = np.random.default_rng(seed)
        opts = [AskTellOptimizer(_space(), optimizer="bayesian",
                                 seed=seed + 1 + i, mc_samples=n_mc)
                for i in range(B)]
        for o in opts:
            _seed_study(o, n_obs, rng)
        rng = np.random.default_rng(seed)
        bank = StudyBank(_space(), B, optimizer="bayesian", seed=seed,
                         mc_samples=n_mc)
        for b in range(B):
            _seed_study(bank.study(b), n_obs, rng)

        loop_asked, bank_asked = [], []

        def loop_setup():
            # failed tells keep n_obs (and every device shape) frozen
            for o, t in loop_asked:
                o.tell_failed(t.id)
            loop_asked.clear()

        def loop_call():
            for o in opts:
                loop_asked.append((o, o.ask(1)[0]))

        def bank_setup():
            for b, ts in enumerate(bank_asked):
                for t in ts:
                    bank.tell_failed(b, t.id)
            bank_asked.clear()

        def bank_call():
            bank_asked.extend(bank.ask_all(1))

        t_loop, t_bank = _interleaved_medians(
            [loop_call, bank_call], reps=reps,
            setups=[loop_setup, bank_setup])
        sps_loop = B / max(t_loop, 1e-12)
        sps_bank = B / max(t_bank, 1e-12)
        speedup = t_loop / max(t_bank, 1e-12)
        _emit(f"multi_study_loop_{B}", t_loop * 1e6,
              f"speedup=1.0x,studies_per_sec={sps_loop:.1f}")
        _emit(f"studies_per_sec_{B}", t_bank * 1e6,
              f"speedup={speedup:.1f}x,studies_per_sec={sps_bank:.1f}")
        results.append((B, speedup))
    return results


def run_retrace_sweep(max_obs=1024, n_mc=64, n_studies=2, seed=0):
    """Grow one bank 64 -> ``max_obs`` observations, asking at every
    bucket edge and at interior points; count jit cache entries beyond
    the one compile each entry point owes per bucket shape."""
    from repro.analysis.sanitizers import no_retrace
    from repro.core import StudyBank
    from repro.core.studybank import _pow2

    bank = StudyBank(_space(), n_studies, optimizer="bayesian", seed=seed,
                     mc_samples=n_mc)
    led = bank.ledger
    rng = np.random.default_rng(seed)

    # n_obs targets: for each bucket edge E (na jumps at n_obs = E where
    # _pow2(E + pend_cap + 1) doubles), visit E-1, E, E+1, plus a mid-bucket
    # point — the within-bucket asks are where a retrace would hide.
    pend_cap, n = 4, 1
    targets = []
    na, k = 64, 59                       # first edge: _pow2(59+5) = 64
    while na <= max_obs:
        edge = na - pend_cap - n         # last n_obs still inside bucket na
        targets += [edge - 1, edge, edge + 1, edge + (edge // 2)]
        na *= 2
    targets = sorted(t for t in set(targets) if 58 <= t <= max_obs - 5)

    propose_buckets, fit_buckets = set(), set()
    # audit the whole sweep with the shared sanitizer (jits=None ->
    # gp.BANK_JITS; base snapshot absorbs the throughput phase that ran
    # in this process); the benchmark turns violations into exit 1
    # itself, so no raise here
    with no_retrace(raise_on_violation=False) as rep:
        for k in targets:
            for b in range(n_studies):
                add = k - int(led.n_observed()[b])
                _seed_study(bank.study(b), add, rng)
            na = _pow2(max(16, k + pend_cap + n))
            propose_buckets.add(na)
            due = ((led.have_fit == 0) |
                   (led.n_observed() - led.n_fit >= bank.refit_every))
            if due.any():
                fit_buckets.add(na)
            # two asks per target: the first may compile (bucket boundary),
            # the second must be a pure cache hit
            for _ in range(2):
                asked = bank.ask_all(n)
                for b, ts in enumerate(asked):
                    for t in ts:
                        bank.tell_failed(b, t.id)
        # expected compiles per staged entry point: one per na bucket it is
        # dispatched at.  prescale_C's shape depends only on mc_samples (one
        # bucket for the whole sweep); absorb never runs (no trial is in
        # flight at ask time); the fit program runs only at fit-due targets.
        nb = len(propose_buckets)
        rep.expected = {"bank_factors": nb, "bank_prescale_X": nb,
                        "bank_prescale_C": 1, "bank_absorb": 0,
                        "bank_dist": nb, "bank_exp": nb, "bank_pick": nb,
                        "fit_hypers_bank": len(fit_buckets)}
    retraces = rep.violations
    detail = rep.detail() or "all=expected"
    _emit("steady_state_retrace", float(retraces),
          f"retraces={retraces},boundaries={nb},{detail}")
    return retraces


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="small grid for smoke runs (retrace sweep stops "
                         "at 256 observations)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write every emitted row as JSON (the CI "
                         "tier-2 job uploads this as BENCH_*.json)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    results = run_throughput(reps=args.reps)
    retraces = run_retrace_sweep(max_obs=256 if args.quick else 1024)
    target = [s for B, s in results if B == 256]
    if target:
        print(f"# CLAIM issue6 'bank ask >= 50x the Python loop at 256 "
              f"studies': {target[0]:.1f}x -> "
              f"{'PASS' if target[0] >= 50.0 else 'FAIL'}")
    print(f"# CLAIM issue6 'zero steady-state retraces across the growth "
          f"sweep': {retraces} -> {'PASS' if retraces == 0 else 'FAIL'}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "multi_study", "rows": ROWS}, f,
                      indent=1)
        print(f"# wrote {len(ROWS)} rows to {args.json}")
    if retraces:
        sys.exit(1)


if __name__ == "__main__":
    main()
