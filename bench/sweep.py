"""Find a configuration's knee once, on the chip: the highest offered rate
at which the asks' backlog does not grow over the window.

    python bench/sweep.py --workload <cell> --rates 2,4,8 --seconds 10 --seed <n>

One process: for each rate in turn the cell's deployment is built afresh
from the seed and warmed (its lengths planned for the cell's own rate, so
each rate starts from the state a run of the cell starts from), then the
rate is offered by a fresh load generator for ``--seconds``.  For each rate it prints the asks
due and completed in the window, the backlog at its middle and at its
close (the backlog grows where the close holds more than the middle),
the asks' 90th and the tells' 95th percentiles and the trials handed out per
second.  The run's
own rates and knee are recorded in the cell's traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench.run import OUT, ROOT, NoChip, _cache_env, device_info  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _cache_env()
    from repro.compile_cache import enable_compile_cache

    from bench.lib import deployment as dep
    from bench.lib import serving
    from bench.lib.cell import (GRACE_S, STARTUP_S, pct_ms, spawn_loadgen,
                                trials_per_s)
    from bench.lib.registry import Registry
    enable_compile_cache()
    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    try:
        device_info(int(cell["chips"]), reg)
    except NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    cfg, mix = reg.config(cell["config"]), reg.mix(cell["traffic"])
    plan = dep.plan_lengths(cfg, mix, args.seed)
    run_dir = OUT / "sweep" / args.workload
    n = int(cfg["ask_n"])
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t_a = time.monotonic()
        httpd, svc, th, url = serving.start(cfg, plan, args.seed,
                                            str(run_dir / "data"))
        serving.warm_up(svc, cfg, plan)
        print(f"sweep: set-up {time.monotonic() - t_a:.1f} s, bucket "
              f"{serving.bucket_of(svc, n)}", flush=True)
        m = dict(mix, rate=rate)
        t_start = time.monotonic() + STARTUP_S
        t0 = t_start + dep.lead_in_s(m)
        t_end = t0 + args.seconds
        job = {"url": url, "mix": m, "cfg": cfg,
               "names": dep.study_names(cfg), "pools": plan["pools"],
               "durations": dep.eval_durations(m, args.seconds),
               "seed": args.seed, "t_start": t_start, "t0": t0,
               "t_end": t_end, "grace_s": GRACE_S,
               "out": str(run_dir / f"requests-{i}.jsonl")}
        (run_dir / "job.json").write_text(json.dumps(job))
        rc = spawn_loadgen(run_dir / "job.json",
                           run_dir / f"loadgen-{i}.log").wait()
        recs = [json.loads(x) for x in open(job["out"])] if rc == 0 else []
        due = [r for r in recs if t0 <= r["due"] < t_end]
        asks = [r for r in due if r["kind"] == "ask"]
        half = t0 + args.seconds / 2
        done_in = sum(r["ok"] and r["done"] <= t_end for r in asks)
        row = {"rate": rate, "asks_due": len(asks), "asks_done": done_in,
               "backlog": len(asks) - done_in,
               "backlog_mid": sum(r["due"] < half and not (
                   r["ok"] and r["done"] <= half) for r in asks),
               "ask_p90_ms": pct_ms(due, ("ask",), 0.90),
               "tell_p95_ms": pct_ms(due, ("tell", "tell_failed"), 0.95),
               "trials_per_s": trials_per_s(recs, t0, t_end),
               "bucket": serving.bucket_of(svc, n)}
        rows.append(row)
        print("sweep: " + json.dumps(row), flush=True)
        httpd.shutdown()
        httpd.server_close()
        th.join(10.0)
        svc.close()
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
