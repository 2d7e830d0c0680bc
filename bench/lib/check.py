"""Whether what the timed path answered is correct.

Once the window has closed and the service is idle, a copy of its data
directory is reopened (snapshot plus journal replay) while a sample of the
window's asks, drawn from the seed, is captured as it replays.  Compared:

  * ``tells_lost``: acknowledged tells that do not read back from the
    reopened copy as acknowledged (observed with the value told, or
    failed);
  * ``answers_mismatch``: trials handed out in a reply whose configuration
    differs in the live or the reopened ledger;
  * ``asks_short``: asks answered with fewer trials than asked for;
  * ``requests_failed``: requests that got an error or no reply by the end
    of the grace period;
  * ``next_ask_mismatch``: studies whose next proposals differ between the
    live service and the reopened copy;
  * ``<family>_pick_gap``: the widest gap of the sampled asks' picks
    against the float64 references (``bench/lib/reference.py``), and
    ``<family>_pick_gap_mean``: the mean of those gaps, per strategy family
    (``gp``, ``cluster``, ``tpe``); the GP family's references fit their
    own hyperparameters, redoing every fit of the study's schedule;
  * ``fit_gap``: the widest shortfall, over the sampled GP-family asks, of
    the service's hyperparameters below the reference's in log marginal
    likelihood per observation; ``fit_count_mismatch``: asks that saw
    another observation count than the journal says, or whose study was
    fit last at another count than the reference's schedule.

``bench/limits/<cell>.json`` says how many asks of each family are
sampled and which of the readings are compared, each with its limit; the
counts' limit is 0.  Every reading is printed on standard error.
"""
from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

from threadpoolctl import threadpool_limits

from bench.lib import deployment as dep
from bench.lib import reference, serving

# BLAS threads of the float64 references: all of a one-chip host's cores
# oversubscribe it while the reopened copy replays beside them
REFERENCE_THREADS = 4
FAMILY = {"bayesian": "gp", "hallucination": "gp", "clustering": "cluster",
          "tpe": "tpe"}


def _json(x):
    return json.loads(json.dumps(x))


def sample_seqs(cfg, limits, records, wal, seed: int, t0: float,
                t_end: float) -> Dict[int, str]:
    """Journal sequence numbers of the asks to judge, per family: asks due
    in the window and answered, drawn from the seed."""
    fam_of = dict(zip(dep.study_names(cfg),
                      [FAMILY[s] for s in dep.strategies(cfg)]))
    seq_of = {(r["study"], r.get("req_id")): r["seq"] for r in wal
              if r["op"] == "ask"}
    names = dep.study_names(cfg)
    rng = dep.rng_for(seed, 6)
    out: Dict[int, str] = {}
    for fam, k in limits["sample"].items():
        pool = sorted(seq_of[(names.index(r["study"]), r["req_id"])]
                      for r in records
                      if r["kind"] == "ask" and r["ok"]
                      and t0 <= r["due"] < t_end
                      and fam_of[r["study"]] == fam
                      and (names.index(r["study"]), r["req_id"]) in seq_of)
        for i in rng.permutation(len(pool))[:int(k)]:
            out[int(pool[int(i)])] = fam
    return out


def run(svc, cfg, limits, records: List[Dict], data_dir: str, copy_dir,
        seed: int, t0: float, t_end: float, n: int,
        control: bool = False) -> Dict[str, Dict]:
    """The checks, each ``{"value", "limit"}``.  Under ``control`` the
    TPE asks are judged on the picks of the bfloat16 reference
    (``reference.tpe_control_picks``) in place of the program's."""
    from repro.service.recovery import WAL_FILE
    from repro.service.server import TuningService

    names = dep.study_names(cfg)
    fams = [FAMILY[s] for s in dep.strategies(cfg)]
    wal = reference.read_wal(os.path.join(data_dir, WAL_FILE))
    want = sample_seqs(cfg, limits, records, wal, seed, t0, t_end)
    study_of = {int(r["seq"]): int(r["study"]) for r in wal
                if r["op"] == "ask"}
    gp_want = {s: study_of[s] for s, fam in want.items() if fam != "tpe"}
    svc_cfg = cfg["service"]
    # the reference's own fits need only the journal and the observations
    # as told, so they run while the copy replays
    pool = ThreadPoolExecutor(max_workers=1)
    with threadpool_limits(REFERENCE_THREADS):
        fits = pool.submit(
            reference.replay_fits, wal, gp_want,
            serving.observations(svc, sorted(set(gp_want.values()))),
            lambda b: fams[b] != "tpe", int(svc_cfg["refit_every"]),
            int(svc_cfg["fit_steps"]))
        copy_dir = str(copy_dir)
        serving.copy_data_dir(data_dir, copy_dir)
        try:
            with serving.capture_asks(want) as got:
                back = TuningService(copy_dir)
        finally:
            refs = fits.result()
            pool.shutdown()
    try:
        live = {nm: {t["id"]: t for t in _json(svc.trials(nm))["trials"]}
                for nm in names}
        led = {nm: {t["id"]: t for t in _json(back.trials(nm))["trials"]}
               for nm in names}
        lost = mismatch = short = 0
        for r in records:
            if not r["ok"]:
                continue
            if r["kind"] == "ask":
                short += len(r["trials"]) != n
                for t in r["trials"]:
                    for book in (live, led):
                        row = book[r["study"]].get(t["id"])
                        mismatch += row is None or row["params"] != t["params"]
            else:
                row = led[r["study"]].get(r["trial_id"])
                ok = row is not None and (
                    row["status"] == "failed" if r["kind"] == "tell_failed"
                    else row["status"] == "observed"
                    and row["value"] == r["value"])
                lost += not ok
        nxt = 0
        for nm in names:
            a = _json(svc.ask(nm, n=n, req_id="check-next")["trials"])
            b = _json(back.ask(nm, n=n, req_id="check-next")["trials"])
            if a != b:
                nxt += 1
                print(f"bench: next ask of {nm}: live {a!r}, reopened {b!r}",
                      file=sys.stderr, flush=True)
    finally:
        back.close()
    checks = {
        "tells_lost": {"value": lost, "limit": 0},
        "answers_mismatch": {"value": mismatch, "limit": 0},
        "asks_short": {"value": short, "limit": 0},
        "requests_failed": {"value": sum(not r["ok"] for r in records),
                            "limit": 0},
        "next_ask_mismatch": {"value": nxt, "limit": 0},
    }
    found = [a for a in got if min(a["picks"]) >= 0]
    for a in found:
        if a["family"] != "tpe":
            a["ref"] = refs[a["seq"]]
    if control:
        for a in found:
            if a["family"] == "tpe":
                a["picks"] = reference.tpe_control_picks(a)
    with threadpool_limits(REFERENCE_THREADS):
        readings, lines = reference.judge(found)
    print(f"bench: reference: {refs['fits']} fits redone", file=sys.stderr,
          flush=True)
    for line in lines:
        print(f"bench: reference: {line}", file=sys.stderr, flush=True)
    complete = (len(found) == len(want)
                and all(sum(a["family"] == fam for a in found) for fam in
                        set(want.values())))
    for name, value in sorted(readings.items()):
        print(f"bench: reading {name}: {value!r}", file=sys.stderr,
              flush=True)
    for name, limit in limits["limits"].items():
        checks[name] = {"value": readings.get(name) if complete else None,
                        "limit": limit}
    return checks
