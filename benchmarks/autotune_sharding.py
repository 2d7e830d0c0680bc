"""Mango tunes the framework's OWN stack (beyond-paper, ROADMAP scenario).

Two searches over the repo's real workload surface, both driven through the
production ``Tuner`` on *conditional* spaces (``Choice`` / ``Int`` /
``LogInt`` / constraint predicates — core/spaces.py):

  1. **Sharding-plan search** — for one config-registry cell
     (arch x shape x mesh size), a conditional space over the
     parallelism layout: the ``parallel`` root picks dp / tp4 / tp8
     (/ ep for MoE archs) and only that branch's knobs exist (``zero``
     matters only under pure-dp; ``capacity_factor`` only under expert
     parallelism).  The objective is ``hlo_cost.estimate_plan`` — the
     analytic roofline estimator (microseconds per plan, no compile) —
     and a constraint predicate rejects plans whose resident HBM
     exceeds the chip.  ``--validate`` re-scores the winner with the
     real lower+compile dry-run pipeline.

  2. **Pallas kernel tile search** — flash_attention (block_q, block_k)
     and ssm_scan (block_d, chunk) tile knobs with a *measured-runtime*
     objective (jit + interpret on CPU; real kernels on TPU), the
     classic block-size autotune shaped as an ask/tell study.

Emits the repo's ``name,us_per_call,derived`` rows (``--json`` for the CI
trajectory):

  autotune_ask_gp        us per GP ask/tell iteration on the conditional
                         space (gated in CI as a ratio to the random row)
  autotune_ask_random    same loop, random search — the same-run
                         normalization denominator (throttling-immune)
  autotune_objective     us per estimate_plan call

  PYTHONPATH=src python benchmarks/autotune_sharding.py --quick --json out.json
  PYTHONPATH=src python benchmarks/autotune_sharding.py --full --validate
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_shape
from repro.core import Tuner, ParamSpace, Choice, LogInt, CHOICE_KEY
from repro.launch.hlo_cost import estimate_plan

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "artifacts" / "autotune"

ROWS = []


def _emit(name, us, derived):
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


# --------------------------------------------------------------------------
# scenario 1: conditional sharding-plan search on the analytic cost model
# --------------------------------------------------------------------------

def sharding_space(cfg, shape, n_devices):
    """(ParamSpace knobs, config->plan mapping, constraints)."""
    branches = {
        "dp": {"zero": ["zero1", "zero3"]},
        "tp4": {"seq_parallel": [0, 1]},
        "tp8": {"seq_parallel": [0, 1]},
    }
    if cfg.n_experts:
        branches["ep"] = {"capacity_factor": [1.0, 1.25, 1.5]}
    space = {
        "parallel": Choice(branches),
        "remat": ["none", "dots", "full"],
        "micro": LogInt(1, 16),
    }

    def plan_of(c):
        p = c["parallel"]
        br = p[CHOICE_KEY]
        plan = {"remat": c["remat"], "micro": int(c["micro"]),
                "zero": p.get("zero", "zero3"),
                "tp": {"dp": 1, "tp4": 4, "tp8": 8, "ep": 1}[br],
                "seq_parallel": bool(p.get("seq_parallel", 0)),
                "ep": br == "ep"}
        if "capacity_factor" in p:
            plan["capacity_factor"] = float(p["capacity_factor"])
        return plan

    constraints = [lambda c: estimate_plan(cfg, shape, plan_of(c),
                                           n_devices)["fits"]]
    return space, plan_of, constraints


def run_sharding(args):
    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    n_dev = args.devices
    space, plan_of, cons = sharding_space(cfg, shape, n_dev)

    def objective(params_list):
        evals, params = [], []
        for par in params_list:
            est = estimate_plan(cfg, shape, plan_of(par), n_dev)
            if est["feasible"]:
                evals.append(-est["t_step_s"])
                params.append(par)
        return evals, params

    iters = 6 if args.quick else 20
    conf = dict(optimizer="bayesian", batch_size=2, num_iteration=iters,
                initial_random=2, seed=args.seed,
                mc_samples=2000 if args.quick else 5000,
                fit_steps=15 if args.quick else 40)

    # timed GP loop (the gated row) + random-search loop (its same-run
    # denominator: runner throttling moves both, the ratio stays clean)
    t0 = time.perf_counter()
    res = Tuner(ParamSpace(space, constraints=cons), objective, conf).maximize()
    t_gp = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_rand = Tuner(ParamSpace(space, constraints=cons), objective,
                     {**conf, "optimizer": "random"}).maximize()
    t_rand = time.perf_counter() - t0

    best_plan = plan_of(res.best_params)
    best = estimate_plan(cfg, shape, best_plan, n_dev)
    _emit("autotune_ask_gp", t_gp / iters * 1e6,
          f"best_step={-res.best_objective:.4f}s")
    _emit("autotune_ask_random", t_rand / iters * 1e6,
          f"best_step={-res_rand.best_objective:.4f}s")

    # objective latency row (cheapness claim: thousands of plans/second)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        estimate_plan(cfg, shape, best_plan, n_dev)
    _emit("autotune_objective", (time.perf_counter() - t0) / reps * 1e6,
          f"cell={args.arch}/{args.shape}/n{n_dev}")

    summary = {
        "cell": f"{args.arch}/{args.shape}/n{n_dev}",
        "best_plan": best_plan,
        "best_step_s": -res.best_objective,
        "best_hbm_gb": round(best["hbm_gb"], 2),
        "dominant": best["dominant"],
        "random_best_step_s": -res_rand.best_objective,
        "trials": len(res.objective_values),
        "gp_vs_random_gain": (
            (-res_rand.best_objective) / max(-res.best_objective, 1e-12)),
    }
    if args.validate:
        summary["dryrun"] = validate_with_dryrun(args, best_plan)
    return summary


def validate_with_dryrun(args, plan):
    """Re-score the winner through the real lower+compile pipeline.

    The subprocess inherits the parent environment unchanged (plus
    PYTHONPATH): the caller's ``JAX_PLATFORMS`` decides its platform.
    """
    tag = "autotune-best"
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", args.arch, "--shape", args.shape, "--mesh", "single",
           "--tag", tag, "--out", str(OUT),
           "--micro", str(plan["micro"]), "--remat", plan["remat"]]
    if plan.get("seq_parallel"):
        cmd.append("--seq-parallel")
    if plan.get("zero") == "zero1":
        cmd.append("--zero1")
    if plan.get("ep"):
        cmd += ["--ep", "--capacity-factor",
                str(plan.get("capacity_factor", 1.25))]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                       env=env, cwd=str(ROOT))
    art = OUT / f"{args.arch}__{args.shape}__single__{tag}.json"
    if p.returncode != 0 or not art.exists():
        return {"error": (p.stdout + p.stderr)[-400:]}
    d = json.loads(art.read_text())
    return {"roofline": d["roofline"], "t_compile_s": d.get("t_compile_s")}


# --------------------------------------------------------------------------
# scenario 2: Pallas kernel tile search, measured-runtime objective
# --------------------------------------------------------------------------

def _measure(make_fn, reps=3):
    """Median seconds/call of a jitted thunk, compile excluded."""
    import jax
    fn = make_fn()
    jax.block_until_ready(fn())  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_kernels(args):
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import sdpa
    from repro.kernels.ssm_scan.ops import selective_scan

    S = 256 if args.quick else 512
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, S, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, 2, 64), jnp.float32)

    Ssm, di, N = (128, 128, 8) if args.quick else (256, 256, 16)
    A = jax.random.uniform(ks[0], (1, Ssm, di, N), jnp.float32, 0.5, 0.999)
    Bx = jax.random.normal(ks[1], (1, Ssm, di, N), jnp.float32) * 0.1
    Cc = jax.random.normal(ks[2], (1, Ssm, N), jnp.float32)

    # one conditional study over both kernels: the Choice root selects the
    # kernel, each branch carries that kernel's tile knobs, and the
    # objective measures the *active* kernel normalized to its own
    # default-tile runtime (so branches are comparable and the argmax is
    # "which kernel gains most from retiling, and with which tiles")
    t_flash0 = _measure(lambda: (lambda: sdpa(q, k, v, causal=True,
                                              block_q=128, block_k=128)))
    t_ssm0 = _measure(lambda: (lambda: selective_scan(
        A, Bx, Cc, block_d=min(512, di), chunk=64)))

    space = {"kernel": Choice({
        "flash_attention": {"block_q": [32, 64, 128, 256],
                            "block_k": [32, 64, 128, 256]},
        "ssm_scan": {"block_d": [32, 64, 128],
                     "chunk": [16, 32, 64]},
    })}
    cons = [lambda c: (c["kernel"].get("block_q", 1) <= S
                       and c["kernel"].get("block_k", 1) <= S
                       and di % c["kernel"].get("block_d", 1) == 0
                       and Ssm % c["kernel"].get("chunk", 1) == 0)]

    measured = {}

    def objective(params_list):
        evals, params = [], []
        for par in params_list:
            kc = par["kernel"]
            if kc[CHOICE_KEY] == "flash_attention":
                bq, bk = kc["block_q"], kc["block_k"]
                t = _measure(lambda: (lambda: sdpa(
                    q, k, v, causal=True, block_q=bq, block_k=bk)))
                rel = t / t_flash0
            else:
                bd, ck = kc["block_d"], kc["chunk"]
                t = _measure(lambda: (lambda: selective_scan(
                    A, Bx, Cc, block_d=bd, chunk=ck)))
                rel = t / t_ssm0
            measured[json.dumps(kc, sort_keys=True)] = t
            evals.append(-rel)
            params.append(par)
        return evals, params

    iters = 4 if args.quick else 12
    res = Tuner(ParamSpace(space, constraints=cons), objective,
                dict(optimizer="bayesian", batch_size=1,
                     num_iteration=iters, initial_random=2, seed=args.seed,
                     mc_samples=2000, fit_steps=10)).maximize()
    best = res.best_params["kernel"]
    return {
        "flash_default_s": t_flash0, "ssm_default_s": t_ssm0,
        "best_kernel_config": best,
        "best_rel_runtime": -res.best_objective,
        "trials": len(res.objective_values),
        "measured": {k: round(v, 5) for k, v in measured.items()},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json")
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--devices", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--validate", action="store_true",
                    help="re-score the sharding winner via the real "
                         "lower+compile dry-run (minutes)")
    ap.add_argument("--skip-kernels", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if not args.full:
        args.quick = True
    OUT.mkdir(parents=True, exist_ok=True)

    t0 = time.time()
    doc = {"sharding": run_sharding(args)}
    if not args.skip_kernels:
        doc["kernels"] = run_kernels(args)
    doc["rows"] = ROWS
    doc["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps({k: v for k, v in doc.items() if k != "rows"},
                     indent=2, default=str))
    if args.json:
        Path(args.json).write_text(json.dumps(doc, indent=2, default=str))


if __name__ == "__main__":
    main()
