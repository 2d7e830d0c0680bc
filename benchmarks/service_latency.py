"""Durable-service serving overhead: HTTP ask/tell vs the in-process bank.

What the durability layer costs per operation.  Three arms, same strategy
and study state:

  * ``inproc_ask``: ``StudyBank`` bank-of-one ``view.ask(1)`` — the raw
    engine, no journal, no HTTP.
  * ``service_ask``: ``TuningService.ask`` called in-process — adds the
    journal-then-apply write path (JSON frame, CRC, fsync) and dedup
    bookkeeping, but no network.
  * ``http_ask``: the same ask through ``ServiceClient`` against a
    ``ThreadingHTTPServer`` on localhost — the full deployment path.

Tell rows mirror the same three arms.  Asks are steady-state: proposals
are resolved (told failed) between timed reps so observation counts and
device shapes stay frozen.  The fsync dominates the service arm by
design — that is the durability price, reported, not hidden.

``--json PATH`` writes rows for the CI perf-trajectory archive.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import threading
import time

ROWS = []


def _emit(name, us, note=""):
    ROWS.append({"name": name, "us_per_call": round(us, 1), "note": note})
    print(f"{name},{us:.1f},{note}", flush=True)


def _median_us(fn, reps=5, calls=20, setup=None):
    samples = []
    for _ in range(reps):
        if setup:
            setup()
        t0 = time.perf_counter()
        for _ in range(calls):
            fn()
        samples.append((time.perf_counter() - t0) / calls)
    samples.sort()
    return samples[len(samples) // 2] * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.core.studybank import StudyBank
    from repro.service.client import ServiceClient
    from repro.service.server import (CrashPoints, TuningService, serve,
                                      space_from_spec)

    CFG = {"space": {"x": {"uniform": [-1.0, 2.0]},
                     "lr": {"loguniform": [1e-4, 1e-1]}},
           "max_studies": 2, "optimizer": "bayesian", "seed": 0,
           "mc_samples": 64, "fit_steps": 8}
    work = tempfile.mkdtemp(prefix="svc_bench_")

    def seed_study(ask, tell, n=12):
        for i in range(n):
            for t in ask():
                tell(t, 0.1 * i)

    # ---- in-process bank (no journal) ---------------------------------
    bank = StudyBank(space_from_spec(CFG["space"]), n_studies=1, seed=0,
                     mc_samples=CFG["mc_samples"],
                     fit_steps=CFG["fit_steps"])
    view = bank.studies[0]
    seed_study(lambda: view.ask(1), lambda t, v: view.tell(t.id, v))
    pend = []

    def inproc_ask():
        pend.extend(view.ask(1))

    def inproc_settle():
        while pend:
            view.tell_failed(pend.pop().id)

    us = _median_us(inproc_ask, calls=args.calls, setup=inproc_settle)
    _emit("service_inproc_ask", us, "bank view, no WAL")

    # ---- service core (WAL fsync, no HTTP) ----------------------------
    svc = TuningService(f"{work}/core", config=CFG, crash=CrashPoints(""))
    svc.create_study("s")
    seed_study(lambda: [type("T", (), t) for t in
                        svc.ask("s", 1)["trials"]],
               lambda t, v: svc.tell("s", t.id, v))
    sp = []

    def svc_ask():
        sp.extend(t["id"] for t in svc.ask("s", 1)["trials"])

    def svc_settle():
        while sp:
            svc.tell_failed("s", sp.pop())

    us = _median_us(svc_ask, calls=args.calls, setup=svc_settle)
    _emit("service_wal_ask", us, "journal-then-apply, fsync")
    ids = [t["id"] for t in svc.ask("s", args.calls)["trials"]]
    t0 = time.perf_counter()
    for tid in ids:
        svc.tell("s", tid, 1.0)
    _emit("service_wal_tell",
          (time.perf_counter() - t0) / len(ids) * 1e6, "fsync per tell")

    # ---- full HTTP path ----------------------------------------------
    httpd, hsvc = serve(f"{work}/http", port=0, config=CFG)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    cl = ServiceClient(f"http://127.0.0.1:{httpd.server_address[1]}")
    cl.create_study("s")
    seed_study(lambda: [type("T", (), t) for t in
                        cl.ask("s", 1)["trials"]],
               lambda t, v: cl.tell("s", t.id, v))
    hp = []

    def http_ask():
        hp.extend(t["id"] for t in cl.ask("s", 1)["trials"])

    def http_settle():
        while hp:
            cl.tell_failed("s", hp.pop())

    us = _median_us(http_ask, calls=args.calls, setup=http_settle)
    _emit("service_http_ask", us, "localhost HTTP round trip")
    ids = [t["id"] for t in cl.ask("s", args.calls)["trials"]]
    t0 = time.perf_counter()
    for tid in ids:
        cl.tell("s", tid, 1.0)
    _emit("service_http_tell",
          (time.perf_counter() - t0) / len(ids) * 1e6, "HTTP + fsync")

    httpd.shutdown()
    hsvc.close()
    svc.close()
    shutil.rmtree(work, ignore_errors=True)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(ROWS, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
