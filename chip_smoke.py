"""One-chip smoke test of the tuning service's main path.

Run from the root of a checkout, on a machine with one TPU:

    python chip_smoke.py

The script starts the durable HTTP service in this process (``serve`` with
``serve_forever`` on a thread) and drives a fleet of 32 studies over an
XGBoost-style search space of 10 parameters, through ``ServiceClient``:
create the studies, seed each with 480 journaled observations, then ask
and tell over HTTP.  It fails unless all of these hold:

  * every HTTP reply is 2xx, and ``/health`` reports no journal error;
  * a service reopened on a copy of the data dir (WAL replay) proposes
    exactly what the live service proposes;
  * a warm round of asks compiles no bank program and makes no implicit
    device-to-host read.

It also runs one fleet-wide ``StudyBank.ask_all`` on the reopened copy, and
prints the peak device memory.  Whether the chip's picks are good is the
benchmark's to judge (``bench/lib/reference.py``).

Earlier lines report phases, timings, compile counts and the compilation
cache.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed on
a TPU.  Without a TPU the script exits nonzero before any phase runs.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

OUT_DIR = ROOT / ".chip_smoke"

# XGBoost-style space, as in Mango's README examples: log-uniform rates
# and regularizers, uniform fractions, integer depths and rounds, and a
# categorical booster (one-hot: 12 encoded dims, 28800 candidates per ask)
SPACE = {
    "learning_rate": {"loguniform": [1e-3, 0.5]},
    "gamma": {"uniform": [0.0, 5.0]},
    "max_depth": {"range": [1, 16]},
    "n_estimators": {"range": [50, 1000, 10]},
    "min_child_weight": {"int": [1, 10]},
    "subsample": {"uniform": [0.5, 0.5]},
    "colsample_bytree": {"uniform": [0.3, 0.7]},
    "reg_alpha": {"loguniform": [1e-4, 10.0]},
    "reg_lambda": {"loguniform": [1e-4, 10.0]},
    "booster": {"choice": ["gbtree", "gblinear", "dart"]},
}
STRATEGY_CYCLE = ("bayesian", "tpe", "clustering")
# the fleet: 480 seeded observations, plus 4 rounds of 4 tells, keep
# every study inside the na = 512 bucket (480 + 16 + pend_cap + n <= 512)
FLEET = dict(studies=32, seed_obs=480, rounds=3, ask_n=4, mc_samples=None)


def require_tpu() -> dict:
    """Print the devices JAX sees and return ``{"platform", "kind",
    "count"}``.  Raises unless the platform is ``tpu``: this smoke test
    measures the chip and never falls back to the CPU."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"devices: {devs}", flush=True)
    print(f"platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX found "
                           f"{info['platform']!r}")
    return info


def objective(p: dict) -> float:
    """Closed-form stand-in for a validation accuracy: smooth in every
    parameter, with its optimum inside the space."""
    lr = math.log10(p["learning_rate"])
    acc = (0.93 - 0.02 * (lr + 1.3) ** 2
           - 0.0015 * (p["max_depth"] - 7) ** 2
           - 0.1 * (p["subsample"] - 0.85) ** 2
           - 0.08 * (p["colsample_bytree"] - 0.7) ** 2
           - 0.004 * p["gamma"]
           - 0.003 * abs(math.log10(p["reg_lambda"]))
           - 0.002 * abs(math.log10(p["reg_alpha"]) + 2)
           - 2e-8 * (p["n_estimators"] - 600) ** 2
           - 0.001 * abs(p["min_child_weight"] - 3))
    return acc + {"gbtree": 0.0, "gblinear": -0.03, "dart": 0.005}[
        p["booster"]]


def _json(x):
    """What ``x`` looks like after a trip over the wire."""
    return json.loads(json.dumps(x))


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def bank_jits() -> dict:
    from repro.core import gp, tpe
    return {**gp.BANK_JITS,
            "fused_tpe_propose_bank": tpe.fused_tpe_propose_bank}


def compiled_programs() -> int:
    return sum(int(f._cache_size()) for f in bank_jits().values())


# --------------------------------------------------------------------------
# the smoke run
# --------------------------------------------------------------------------
class Run:
    """Phase bookkeeping: wall time per phase, and every failure."""

    def __init__(self):
        self.phases: dict = {}
        self.failures: list = []
        self.facts: dict = {}

    def phase(self, name: str, fn):
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 — reported, fails the run
            self.failures.append(f"{name}: {type(e).__name__}: {e}")
            traceback.print_exc()
            out = None
        dt = time.perf_counter() - t0
        self.phases[name] = dt
        print(f"phase {name}: {dt:.3f} s "
              f"{'FAILED' if out is None else 'ok'}", flush=True)
        return out

    def check(self, ok: bool, msg: str) -> None:
        if not ok:
            self.failures.append(msg)
            print(f"CHECK FAILED: {msg}", flush=True)


def run(out_dir: Path = OUT_DIR, studies: int = 32, seed_obs: int = 480,
        rounds: int = 3, ask_n: int = 4, mc_samples=None,
        seed: int = 0) -> Run:
    import jax

    from repro.analysis.sanitizers import no_retrace, no_transfer
    from repro.compile_cache import enable_compile_cache
    from repro.core.spaces import ParamSpace
    from repro.core.studybank import _pow2
    from repro.service.client import ServiceClient
    from repro.service.server import TuningService, serve, space_from_spec

    R = Run()
    cache_dir = enable_compile_cache()
    R.facts["cache_dir"] = cache_dir
    R.facts["cache_entries_before"] = cache_entries(cache_dir)
    print(f"compilation cache: {cache_dir} "
          f"({R.facts['cache_entries_before']} files)", flush=True)

    space = ParamSpace(space_from_spec(SPACE))
    S = mc_samples or space.mc_samples(ask_n)
    names = [f"study-{i:02d}" for i in range(studies)]
    n_gp = sum(STRATEGY_CYCLE[i % 3] != "tpe" for i in range(studies))
    print(f"fleet: {studies} studies ({n_gp} GP-family), space dim "
          f"{space.dim}, S={S} candidates per ask, ask n={ask_n}",
          flush=True)

    shutil.rmtree(out_dir, ignore_errors=True)
    data_dir, copy_dir = out_dir / "data", out_dir / "data_copy"
    config = {"space": SPACE, "max_studies": studies, "seed": seed,
              "mc_samples": mc_samples, "compact_every_ops": 0}
    httpd, svc = serve(data_dir, port=0, config=config)
    server = threading.Thread(target=httpd.serve_forever, daemon=True,
                              name="chip-smoke-http")
    server.start()
    host, port = httpd.server_address[:2]
    client = ServiceClient(f"http://{host}:{port}", timeout=900.0,
                           retries=0)
    replies = {"n": 0}

    def call(method, *a, **kw):
        out = getattr(client, method)(*a, **kw)  # raises on any non-2xx
        replies["n"] += 1
        return out

    rng = np.random.default_rng(seed)
    fail_every = 11

    def create():
        for i, name in enumerate(names):
            call("create_study", name, sign=1.0,
                 optimizer=STRATEGY_CYCLE[i % 3])
        return True

    def seed_fleet():
        for name in names:
            for p in space.sample(seed_obs, rng):
                svc.observe(name, p, objective(p))
        call("compact")
        return True

    counter = {"tells": 0}

    def ask_round():
        got = {}
        for name in names:
            got[name] = call("ask", name, n=ask_n)["trials"]
        for name in names:
            for t in got[name]:
                counter["tells"] += 1
                if counter["tells"] % fail_every == 0:
                    call("tell_failed", name, t["id"])
                else:
                    call("tell", name, t["id"], objective(t["params"]))
        return True

    R.phase("create", create)
    R.phase("seed", seed_fleet)
    for r in range(rounds):
        R.phase(f"round{r + 1}", ask_round)
    R.facts["programs_after_warmup"] = compiled_programs()
    print(f"bank programs compiled after warm-up: "
          f"{R.facts['programs_after_warmup']}", flush=True)

    def steady():
        with no_retrace(bank_jits(), raise_on_violation=False) as rep, \
                no_transfer(all_threads=True):
            ask_round()
        R.facts["steady_compiles"] = rep.violations
        R.check(rep.violations == 0,
                f"steady round compiled bank programs: {rep.detail()}")
        return True

    R.phase("steady", steady)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    R.facts["peak_bytes_served"] = stats.get("peak_bytes_in_use")
    R.facts["bytes_limit"] = stats.get("bytes_limit")

    def restart():
        shutil.copytree(data_dir, copy_dir)
        reopened = TuningService(copy_dir)
        try:
            rep = reopened.recovery
            print(f"recovery: {rep.replayed} ops replayed from the WAL over "
                  f"the snapshot", flush=True)
            R.check(reopened.bank.op_seq == call("health")["op_seq"],
                    "reopened op_seq differs from the live service")
            same = 0
            for name in names:
                R.check(_json(reopened.trials(name)) == call("trials", name),
                        f"{name}: reopened trial ledger differs")
                live = call("ask", name, n=ask_n, req_id=f"restart-{name}")
                back = reopened.ask(name, n=ask_n, req_id=f"restart-{name}")
                ok = _json(back["trials"]) == live["trials"]
                same += ok
                R.check(ok, f"{name}: reopened proposals differ")
            R.facts["restart_bit_equal"] = f"{same}/{len(names)}"
            fleet(reopened)
        finally:
            reopened.close()
        return True

    def fleet(reopened):
        # one fleet-wide batch on the throwaway copy: every family's
        # (rows, S, na) dispatch at once (not journaled; the copy is
        # deleted below)
        t0 = time.perf_counter()
        out = reopened.bank.ask_all(ask_n)
        R.facts["fleet_ask_all_s"] = time.perf_counter() - t0
        R.check(all(len(ts) == ask_n for ts in out),
                "fleet ask_all returned short batches")

    R.phase("restart", restart)
    stats = dev.memory_stats() or {}
    R.facts["peak_bytes_fleet"] = stats.get("peak_bytes_in_use")

    def health():
        h = call("health")
        R.check(h["wal_error"] is None and h["status"] == "ok",
                f"health: {h}")
        return True

    R.phase("health", health)
    httpd.shutdown()
    httpd.server_close()
    svc.close()
    R.facts["http_replies_2xx"] = replies["n"]

    # the bucket every ask ran in (the bank's own rule; pend_cap is 4)
    R.facts["bucket_na"] = _pow2(
        max(16, seed_obs + ask_n * (rounds + 1) + 4 + ask_n))
    R.facts["cache_entries_after"] = cache_entries(cache_dir)
    R.facts["phases_s"] = R.phases
    print(f"memory: served path peak {R.facts['peak_bytes_served']} B "
          f"(one study per ask); after fleet ask_all peak "
          f"{R.facts['peak_bytes_fleet']} B; limit "
          f"{R.facts['bytes_limit']} B", flush=True)
    print(f"compilation cache: {R.facts['cache_entries_before']} -> "
          f"{R.facts['cache_entries_after']} files", flush=True)
    shutil.rmtree(data_dir, ignore_errors=True)
    shutil.rmtree(copy_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "report.json").write_text(json.dumps(
        {"facts": R.facts, "failures": R.failures}, indent=1, default=str))
    return R


def main() -> int:
    info = require_tpu()
    R = run(**FLEET)
    print("report: " + json.dumps(R.facts, default=str), flush=True)
    if R.failures:
        print(f"FAILED: {len(R.failures)} check(s)", flush=True)
        for f in R.failures:
            print(f"  {f}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
