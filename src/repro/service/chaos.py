"""Chaos harness: SIGKILL the tuning service at seeded points, restart,
and prove recovery is *exact*.

The harness runs the service as a subprocess and drives a deterministic
multi-study ask/tell workload against it over HTTP.  For each of
``--kills`` phases it arms one crash point (``REPRO_SERVICE_CRASH``,
derived from ``random.Random(seed * 1_000_003 + phase)`` — the same
per-task seeding idiom as ``scheduler.distributed.FaultInjection``, so
the kill schedule is a pure function of the seed).  When the process dies
mid-call, the harness restarts it and *re-issues the interrupted request
verbatim* — same ``req_id``, same trial id — exercising every recovery
guarantee at once: torn-tail truncation, WAL suffix replay over the
snapshot, ask dedup, tell dedup.

After the workload, one final crash-free restart (proving recovery is
idempotent) answers the comparison's reads and is stopped.  Then an
uninterrupted oracle service, with no crash points, runs the identical
script in a second data dir over HTTP, and the harness asserts:

  * ``op_seq`` equal — no journaled op was lost or double-counted;
  * every study's full trial ledger (ids, params, status, values) is
    JSON-equal — no tell double-applied, no proposal re-drawn;
  * the *next* proposals from both services are bit-equal — the
    recovered optimizer state (RNG streams, GP fit schedule) is exact,
    not merely consistent.

Only one service process runs at a time, and the harness itself never
imports JAX: on an accelerator the service holds the chip, which belongs
to one process at a time.  Each service inherits the harness's
environment unchanged.

Exit code 0 = all phases passed; on failure the data dirs (WAL +
snapshots) are left in place as artifacts.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.service.client import ServiceClient, ServiceDown, ServiceError
from repro.service.wal import atomic_write_text

# tags eligible for a seeded kill; indices stay small so every spec fires
# within one phase's slice of the workload
KILL_TAGS = [
    ("ask.mid_journal", 2),        # (tag, index upper bound)
    ("ask.after_journal", 2),
    ("tell.mid_journal", 3),
    ("tell.after_journal", 3),
    ("tell.after_apply", 3),
    ("tell_failed.after_journal", 1),
    ("compact.before_snapshot", 1),
    ("compact.after_snapshot", 1),
    ("compact.after_truncate", 1),
    ("compact.background", 1),     # dies inside the compactor daemon
]

DEFAULT_CONFIG = {
    "space": {"x": {"uniform": [-2.0, 4.0]},
              "lr": {"loguniform": [1e-4, 1e-1]}},
    "max_studies": 8,
    "optimizer": "bayesian",
    "seed": 0,
    "mc_samples": 32,
    "fit_steps": 4,
    "refit_every": 4,
    "compact_every_ops": 10,       # arms the background compactor
}

# the heterogeneous fleet the workload provisions: one bank serves all
# three families, sub-batched inside each ask_all
STRATEGY_CYCLE = ["bayesian", "tpe", "clustering"]


def kill_specs(seed: int, kills: int) -> List[str]:
    """One ``tag:index`` spec per phase, a pure function of the seed."""
    specs = []
    for i in range(kills):
        rng = random.Random(seed * 1_000_003 + i)
        tag, bound = KILL_TAGS[rng.randrange(len(KILL_TAGS))]
        specs.append(f"{tag}:{rng.randrange(bound)}")
    return specs


# --------------------------------------------------------------- workload
class Workload:
    """Deterministic script of service calls.  ``run_step`` executes one
    step against any executor (HTTP client or in-process service) and
    keeps per-study trial bookkeeping, so the oracle and the chaos run
    issue byte-identical request sequences."""

    def __init__(self, seed: int, studies: int, rounds: int, batch: int):
        self.seed = seed
        self.names = [f"s{i}" for i in range(studies)]
        self.rounds = rounds
        self.batch = batch
        self._value_seq = 0

    def _value(self) -> float:
        v = random.Random(self.seed * 1_000_003
                          + 7_777_777 + self._value_seq).uniform(-2.0, 2.0)
        self._value_seq += 1
        return v

    def steps(self):
        """Yields (kind, name, payload) tuples.  Tell steps reference ask
        replies positionally: trial ids are minted sequentially per study,
        so id = round*batch + slot deterministically."""
        for i, name in enumerate(self.names):
            yield ("create", name,
                   {"sign": -1.0 if i % 2 else 1.0,
                    "optimizer": STRATEGY_CYCLE[i % len(STRATEGY_CYCLE)]})
        for r in range(self.rounds):
            for s, name in enumerate(self.names):
                yield ("ask", name, {"n": self.batch,
                                     "req_id": f"r{r}s{s}"})
                for slot in range(self.batch):
                    tid = r * self.batch + slot
                    # every 7th resolution is a failure (deterministic)
                    if (r * self.batch + slot + s) % 7 == 3:
                        yield ("tell_failed", name, {"trial_id": tid})
                    else:
                        yield ("tell", name, {"trial_id": tid,
                                              "value": self._value()})
                yield ("trace", name, {})
            yield ("compact", None, {})


def exec_step(ex, step: Tuple[str, Optional[str], Dict[str, Any]]):
    kind, name, p = step
    if kind == "create":
        return ex.create_study(name, sign=p["sign"],
                               optimizer=p.get("optimizer"))
    if kind == "ask":
        return ex.ask(name, n=p["n"], req_id=p["req_id"])
    if kind == "tell":
        return ex.tell(name, p["trial_id"], p["value"])
    if kind == "tell_failed":
        return ex.tell_failed(name, p["trial_id"])
    if kind == "trace":
        return ex.trace(name)
    if kind == "compact":
        return ex.compact()
    raise ValueError(kind)


# ------------------------------------------------------------- subprocess
class ServerProc:
    def __init__(self, data_dir: str, config_path: Optional[str],
                 crash_spec: str = ""):
        env = dict(os.environ)
        if crash_spec:
            env["REPRO_SERVICE_CRASH"] = crash_spec
        else:
            env.pop("REPRO_SERVICE_CRASH", None)
        cmd = [sys.executable, "-m", "repro.service.server",
               "--data-dir", data_dir, "--port", "0"]
        if config_path:
            cmd += ["--config", config_path]
        self.proc = subprocess.Popen(cmd, env=env,
                                     stdout=subprocess.PIPE,
                                     stderr=subprocess.STDOUT, text=True)
        self.base_url = self._await_serving()

    def _await_serving(self, timeout: float = 180.0) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"server exited during startup "
                    f"(rc={self.proc.poll()})")
            if line.startswith("SERVING "):
                _, host, port = line.split()[:3]
                return f"http://{host}:{port}"
        raise RuntimeError("server did not print SERVING in time")

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait_dead(self, timeout: float = 10.0) -> bool:
        try:
            self.proc.wait(timeout)
            return True
        except subprocess.TimeoutExpired:
            return False

    def kill(self) -> None:
        if self.alive():
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait()


# ---------------------------------------------------------------- compare
def collect(client: ServiceClient, names: List[str],
            batch: int) -> Dict[str, Any]:
    """What the comparison reads from one service, over HTTP: its op_seq,
    every study's trial ledger, and the *next* ``2 * batch`` proposals of
    each study (asked in name order, so both services see the same op
    stream)."""
    return {"op_seq": client.health()["op_seq"],
            "trials": {n: client.trials(n)["trials"] for n in names},
            "next": {n: client.ask(n, n=2 * batch)["trials"]
                     for n in names}}


def compare(svc: Dict[str, Any], orc: Dict[str, Any]) -> List[str]:
    failures: List[str] = []
    if svc["op_seq"] != orc["op_seq"]:
        failures.append(f"op_seq diverged: service {svc['op_seq']} "
                        f"vs oracle {orc['op_seq']}")
    for name in orc["trials"]:
        t_svc, t_orc = svc["trials"][name], orc["trials"][name]
        if t_svc != t_orc:
            failures.append(f"{name}: trial ledger diverged "
                            f"(dedup violated or replay drifted)")
            for a, b in zip(t_svc, t_orc):
                if a != b:
                    failures.append(f"  first diff: {a!r} != {b!r}")
                    break
        # remaining proposals must be bit-equal: the recovered RNG/GP
        # state, not just the ledger, is exact
        p_svc, p_orc = svc["next"][name], orc["next"][name]
        if p_svc != p_orc:
            failures.append(f"{name}: post-recovery proposals diverged")
            failures.append(f"  service: {p_svc!r}")
            failures.append(f"  oracle:  {p_orc!r}")
    return failures


# ------------------------------------------------------------------ main
def run(data_dir: str, kills: int = 5, seed: int = 0, studies: int = 3,
        rounds: int = 6, batch: int = 2,
        config: Optional[Dict[str, Any]] = None,
        verbose: bool = True) -> Dict[str, Any]:
    cfg = dict(config or DEFAULT_CONFIG)
    cfg["seed"] = seed
    os.makedirs(data_dir, exist_ok=True)
    svc_dir = os.path.join(data_dir, "service")
    oracle_dir = os.path.join(data_dir, "oracle")
    cfg_path = os.path.join(data_dir, "config.json")
    atomic_write_text(cfg_path, json.dumps(cfg))
    names = [f"s{i}" for i in range(studies)]

    def say(msg):
        if verbose:
            print(msg, flush=True)

    specs = kill_specs(seed, kills)
    say(f"chaos: kill schedule {specs}")

    steps = list(Workload(seed, studies, rounds, batch).steps())
    fired: List[str] = []
    pos = 0
    phase = 0
    server = ServerProc(svc_dir, cfg_path,
                        specs[phase] if phase < len(specs) else "")
    client = ServiceClient(server.base_url, timeout=60.0, retries=0)
    while pos < len(steps):
        step = steps[pos]
        try:
            exec_step(client, step)
            pos += 1
        except ServiceDown:
            if not server.wait_dead(timeout=15.0):
                server.kill()
                raise RuntimeError(
                    f"call failed but server still alive at step {pos} "
                    f"({step[0]}) — not a crash-point death")
            say(f"chaos: killed at step {pos} ({step[0]}) by "
                f"{specs[phase]}; restarting")
            fired.append(specs[phase])
            phase += 1
            server = ServerProc(
                svc_dir, None, specs[phase] if phase < len(specs) else "")
            client = ServiceClient(server.base_url, timeout=60.0, retries=0)
            # re-issue the interrupted step verbatim: dedup must absorb it
    # a spec may not fire if the workload ran out first — report, and the
    # bit-equality checks below still hold for however many fired
    if phase < len(specs):
        say(f"chaos: {len(specs) - phase} spec(s) never fired: "
            f"{specs[phase:]}")
    server.kill()

    # final crash-free restart: recovery must be idempotent (replaying an
    # already-recovered dir changes nothing).  One service runs at a time
    # and this process never touches JAX: on an accelerator the service
    # holds the chip, which belongs to one process at a time.
    server = ServerProc(svc_dir, None, "")
    try:
        got = collect(ServiceClient(server.base_url, timeout=60.0,
                                    retries=2), names, batch)
    finally:
        server.kill()

    say("chaos: running uninterrupted oracle")
    server = ServerProc(oracle_dir, cfg_path, "")
    try:
        oracle = ServiceClient(server.base_url, timeout=60.0, retries=2)
        for step in Workload(seed, studies, rounds, batch).steps():
            exec_step(oracle, step)
        want = collect(oracle, names, batch)
    finally:
        server.kill()

    failures = compare(got, want)
    report = {"kills_requested": kills, "kills_fired": len(fired),
              "fired": fired, "steps": len(steps), "failures": failures}
    say(f"chaos: {len(fired)}/{kills} kills fired over {len(steps)} steps; "
        f"{'PASS' if not failures else 'FAIL'}")
    for f in failures:
        say(f"  {f}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="SIGKILL chaos harness for the durable tuning service")
    ap.add_argument("--data-dir", required=True,
                    help="work dir; service/ and oracle/ land here and are "
                         "left as artifacts on failure")
    ap.add_argument("--kills", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--studies", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args(argv)
    report = run(args.data_dir, kills=args.kills, seed=args.seed,
                 studies=args.studies, rounds=args.rounds, batch=args.batch)
    return 1 if report["failures"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
