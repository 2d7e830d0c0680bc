"""Worker pools in an open loop: the ``worker_pools`` traffic kind.

Each study has a pool of workers.  A worker asks for ``ask_n`` trials,
evaluates them for a duration D, tells each result (a share of them as
``tell_failed``, as crashed trials) and asks again.  Due times follow the
worker's own schedule, each ask due one D after the previous one, and not
the replies: a stall leaves the offered load as it was, and every request
is timed from when it was due.  The rate is ``workers / E[D]``, steady
over the run.  The schedule (first due
times, evaluation times, which trials crash) is the same for every seed;
the seed sets the objective's noise.

Runs in the load generator's process, which never imports JAX.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

from bench.lib.deployment import SCHEDULE, mean_eval_s, objective, rng_for


def worker_plan(job: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each worker's study, first due time and evaluation times, the same
    for every seed.  The first asks are spread evenly over one mean
    evaluation time, so the offered rate is the mix's rate from the start
    and no burst of first asks runs into the window."""
    mix = job["mix"]
    assign = [b for b, k in enumerate(job["pools"]) for _ in range(k)]
    w = len(assign)
    order = rng_for(SCHEDULE, 5).permutation(w)
    ed = mean_eval_s(mix)
    return [{"study": assign[i],
             "first_due": job["t_start"] + (int(order[i]) + 0.5) / w * ed,
             "durations": job["durations"][i]} for i in range(w)]


def worker_loop(job: Dict[str, Any], w: int, plan: Dict[str, Any],
                client, records: list, clock=time.monotonic,
                sleep=time.sleep) -> None:
    """One worker's requests.  ``records`` gets one dict per request with
    its due, send and reply times; ``clock``/``sleep`` are injectable so
    the due-time accounting can be tested under a simulated stall."""
    mix, cfg = job["mix"], job["cfg"]
    name = job["names"][plan["study"]]
    n = int(cfg["ask_n"])
    fail_share = float(mix["fail_share"])
    sd = float(cfg["objective"].get("noise_sd", 0.0))
    crash = rng_for(SCHEDULE, 7, w)         # which trials crash: fixed
    rng = rng_for(job["seed"], 4, w)        # the objective's noise
    t_end, t_stop = job["t_end"], job["t_end"] + job["grace_s"]
    due = plan["first_due"]
    ready = clock()

    def call(kind, due_t, fn, **fields):
        nonlocal ready
        now = clock()
        if due_t > now:
            sleep(due_t - now)
        sent = clock()
        rec = {"w": w, "kind": kind, "study": name, "due": due_t,
               "sent": sent, "late": sent - max(due_t, ready), **fields}
        if sent > t_stop:
            rec.update(done=None, ok=False, error="not sent before the "
                       "grace period ended")
            records.append(rec)
            return None
        try:
            out = fn()
            rec.update(done=clock(), ok=True)
        except Exception as e:  # noqa: BLE001 — recorded as a failure
            out = None
            rec.update(done=clock(), ok=False,
                       error=f"{type(e).__name__}: {e}")
        ready = rec["done"]
        records.append(rec)
        return rec, out

    for k, dur in enumerate(plan["durations"]):
        if due >= t_end:
            return
        rid = f"w{w}-a{k}"
        got = call("ask", due, lambda: client.ask(name, n=n, req_id=rid),
                   req_id=rid)
        if got is None:
            return
        rec, out = got
        if out is not None:
            rec["trials"] = [{"id": t["id"], "params": t["params"]}
                             for t in out["trials"]]
        due_tell = due + dur
        for t in (out or {"trials": []})["trials"]:
            failed = bool(crash.random() < fail_share)
            value = objective(cfg["objective"], t["params"]) + float(
                rng.normal(0.0, sd))
            if due_tell >= t_end:
                continue
            tid = int(t["id"])
            if failed:
                got = call("tell_failed", due_tell,
                           lambda: client.tell_failed(name, tid),
                           trial_id=tid)
            else:
                got = call("tell", due_tell,
                           lambda: client.tell(name, tid, value),
                           trial_id=tid, value=value)
            if got is None:
                return
        due = due_tell


def run(job: Dict[str, Any], client_factory) -> List[Dict[str, Any]]:
    """Start every worker on its own thread and return all records once
    each has stopped (after its last request due in the window, or when
    the grace period after the close runs out)."""
    records: List[Dict[str, Any]] = []
    threads = []
    for w, plan in enumerate(worker_plan(job)):
        th = threading.Thread(target=worker_loop,
                              args=(job, w, plan, client_factory(), records),
                              name=f"worker-{w}", daemon=True)
        threads.append(th)
    for th in threads:
        th.start()
    deadline = job["t_end"] + job["grace_s"] + 30.0
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    return list(records)
