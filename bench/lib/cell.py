"""One run of one cell: set-up, the measured window, the metrics and the
correctness check."""
from __future__ import annotations

import json
import math
import shutil
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from bench.lib import deployment as dep

GRACE_S = 60.0      # answers due in the window are waited for this long
STARTUP_S = 2.0     # the load generator's process start, before the lead-in
TRACE_S = 10.0      # the longest traced slice, from a quarter into the window


# ------------------------------------------------------ end-to-end metrics
def pct_ms(records: List[Dict], kinds, q: float) -> Optional[float]:
    """The ``q`` quantile (0..1) of the latency, from due time to reply,
    of the requests of ``kinds`` due in the window; one that failed or was
    never answered counts as infinitely late."""
    lat = [((r["done"] - r["due"]) * 1e3 if r["ok"] else math.inf)
           for r in records if r["kind"] in kinds]
    if not lat:
        return None
    lat.sort()
    pos = q * (len(lat) - 1)
    lo, hi = lat[int(math.floor(pos))], lat[int(math.ceil(pos))]
    if math.isinf(hi):
        return math.inf if math.isinf(lo) or pos == int(pos) else hi
    return lo + (hi - lo) * (pos - math.floor(pos))


def trials_per_s(all_records: List[Dict], t0: float, t_end: float) -> float:
    """Trials handed out by the asks that completed in the window."""
    n = sum(len(r.get("trials", ())) for r in all_records
            if r["kind"] == "ask" and r["ok"] and t0 <= r["done"] <= t_end)
    return n / (t_end - t0)


def lateness(records: List[Dict]) -> Dict[str, float]:
    late = sorted(r["late"] for r in records)
    if not late:
        return {}
    return {"p50_ms": late[len(late) // 2] * 1e3,
            "p99_ms": late[int(0.99 * (len(late) - 1))] * 1e3,
            "max_ms": late[-1] * 1e3}


# --------------------------------------------------------------- counting
class CompileCounter:
    """XLA executables compiled or loaded from the persistent cache while
    ``active``: a program that compiles inside the window shows here."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.count = 0
        self._mon = mon
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.active and name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, name, _secs, **_):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def close(self):
        self._mon.unregister_event_listener(self._event)
        self._mon.unregister_event_duration_listener(self._duration)


def bank_jits() -> Dict[str, Any]:
    from repro.core import gp, tpe
    return {**gp.BANK_JITS,
            "fused_tpe_propose_bank": tpe.fused_tpe_propose_bank}


def spawn_loadgen(job_path: Path, log_path: Path) -> subprocess.Popen:
    here = Path(__file__).resolve().parent
    with open(log_path, "w") as log:
        return subprocess.Popen(
            [sys.executable, str(here / "loadgen.py"), str(job_path)],
            stdout=log, stderr=subprocess.STDOUT, cwd=str(here.parents[1]))


def _sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        time.sleep(dt)


# -------------------------------------------------------------------- run
def run_cell(reg, workload: str, seed: int, seconds: float, trace: bool,
             device: Dict[str, Any], t_process: float, out: Path,
             fault: Optional[str] = None,
             control: bool = False) -> Dict[str, Any]:
    """Set up, measure and check one run; returns the result line's dict
    (its ``checks`` entry holds each number compared beside its limit).
    ``fault`` breaks the timed path underneath (``bench/lib/faults.py``);
    ``control`` runs the control instead of the program: the GP programs
    at ``"default"`` precision and the bfloat16 TPE reference."""
    import contextlib

    import jax

    from repro.analysis.sanitizers import no_retrace

    from bench.lib import check, serving
    from bench.lib.faults import changed

    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    mix = reg.mix(cell["traffic"])
    limits = reg.limits(workload)
    run_dir = out / "runs" / workload
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    data_dir = str(run_dir / "data")
    plan = dep.plan_lengths(cfg, mix, seed)
    n = int(cfg["ask_n"])
    counter = CompileCounter()
    jits = bank_jits()
    ctx = (changed(fault, "default" if control else None)
           if fault or control else contextlib.nullcontext())
    with ctx:
        t_a = time.monotonic()
        httpd, svc, th, url = serving.start(cfg, plan, seed, data_dir)
        t_b = time.monotonic()
        warm = serving.warm_up(svc, cfg, plan)
        t_c = time.monotonic()
        na = serving.bucket_of(svc, n)
        print(f"bench: set-up: imports and devices {t_a - t_process:.3f} s,"
              f" seeding {t_b - t_a:.3f} s, warm-up {t_c - t_b:.3f} s",
              file=sys.stderr, flush=True)
        if trace:
            serving.install_spans(svc)
        t_start = time.monotonic() + STARTUP_S
        t0 = t_start + dep.lead_in_s(mix)
        t_end = t0 + float(seconds)
        job = {"url": url, "mix": mix, "cfg": cfg,
               "names": dep.study_names(cfg), "pools": plan["pools"],
               "durations": dep.eval_durations(mix, float(seconds)),
               "seed": int(seed), "t_start": t_start, "t0": t0,
               "t_end": t_end, "grace_s": GRACE_S,
               "out": str(run_dir / "requests.jsonl")}
        job_path = run_dir / "job.json"
        job_path.write_text(json.dumps(job))
        proc = spawn_loadgen(job_path, run_dir / "loadgen.log")
        trace_dir = run_dir / "trace"
        window = {}
        with no_retrace(jits, raise_on_violation=False) as rep:
            counter.active = True
            setup_s = t0 - t_process
            if trace:
                _sleep_until(t0 + float(seconds) / 4.0)
                jax.profiler.start_trace(str(trace_dir))
                a = time.monotonic()
                _sleep_until(a + min(TRACE_S, float(seconds) / 2.0))
                window["s"] = time.monotonic() - a
                jax.profiler.stop_trace()
            try:
                rc = proc.wait(timeout=max(1.0, t_end + GRACE_S + 60.0
                                           - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait()
            counter.active = False
        counter.close()
        stats = jax.devices()[0].memory_stats() or {}
        httpd.shutdown()
        httpd.server_close()
        th.join(10.0)
        records = []
        if rc == 0:
            with open(job["out"]) as fh:
                records = [json.loads(line) for line in fh]
        due = [r for r in records if t0 <= r["due"] < t_end]
        print(f"bench: {workload} seed {seed}: bucket na={na} (planned "
              f"{plan['na']}), lengths {min(plan['lengths'])}.."
              f"{max(plan['lengths'])}, warm-up asks {warm['asks']}, "
              f"{len(records)} requests ({len(due)} due in the window), "
              f"generator exit {rc}, lateness {lateness(due)}",
              file=sys.stderr, flush=True)
        t_d = time.monotonic()
        checks = check.run(svc, cfg, limits, records, data_dir,
                           run_dir / "copy", seed, t0, t_end, n, control)
        print(f"bench: check {time.monotonic() - t_d:.3f} s", file=sys.stderr,
              flush=True)
    checks["window_compiles"] = {"value": counter.count, "limit": 0}
    checks["bank_compiles"] = {"value": rep.violations, "limit": 0}
    checks["bucket_moved"] = {"value": int(na != plan["na"]), "limit": 0}
    checks["generator_failed"] = {"value": int(rc != 0), "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    dev = dict(device, memory_peak_bytes=stats.get("peak_bytes_in_use"))
    metrics: Dict[str, Any] = {}
    e2e = {"ask_p90_ms": pct_ms(due, ("ask",), 0.90),
           "tell_p95_ms": pct_ms(due, ("tell", "tell_failed"), 0.95),
           "trials_per_s": trials_per_s(records, t0, t_end),
           "setup_s": setup_s}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": len(due),
                              "failed": sum(not r["ok"] for r in due)}
    if not trace:
        for m in reg.end_to_end(workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench.lib import trace as tr
        events = tr.events_from_xplane(str(trace_dir))
        window_ns = window["s"] * 1e9
        (run_dir / "spans.json").write_text(json.dumps(events))
        rctx = {"events": events, "window_ns": window_ns,
                "peaks": reg.peaks(device["kind"]) or {},
                "e2e": e2e, "records": due}
        for m in reg.per_layer(workload):
            v = reg.reader(m["name"])(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=tr.busy_s(events, window_ns), window_s=window["s"])
        result["breakdown"] = tr.breakdown(events, window_ns)
    svc.close()
    result.update(metrics=metrics, device=dev, checks=checks)
    return result

