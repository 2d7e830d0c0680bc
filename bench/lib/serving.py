"""The system under test, in this process: the durable HTTP service built
from a cell's deployment, seeded in bulk and warmed on the cell's shapes.
Also the benchmark's own spans around the calls into each layer, and the
capture of sampled asks while a copy of the data directory replays.
"""
from __future__ import annotations

import contextlib
import functools
import os
import shutil
import threading
from typing import Any, Dict, List

import numpy as np

from bench.lib import deployment as dep


def service_config(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    return {"space": cfg["space"], "max_studies": int(cfg["studies"]),
            "seed": int(seed) % (1 << 31), **cfg["service"]}


def start(cfg, plan, seed: int, data_dir: str):
    """Serve the deployment over localhost HTTP: create the studies, seed
    each with its history straight through the bank's journal-replay
    entry (no fsync per observation), snapshot it, and return ``(httpd,
    service, thread, url)``.  The history reaches the disk in that one
    snapshot; everything after it, the warm-up too, is journaled."""
    from repro.service.server import serve

    shutil.rmtree(data_dir, ignore_errors=True)
    httpd, svc = serve(data_dir, port=0, config=service_config(cfg, seed))
    # ``server_close`` then waits for the requests still in service when
    # the load generator stops, before the check copies the data directory
    httpd.daemon_threads = False
    names = dep.study_names(cfg)
    for name, strat in zip(names, dep.strategies(cfg)):
        svc.create_study(name, sign=1.0, optimizer=strat)
    history = dep.seeded_history(cfg, plan["lengths"], seed)
    with svc._lock:
        for b, obs in enumerate(history):
            for params, value in obs:
                svc._apply_record({"op": "observe", "study": b,
                                   "params": params, "value": value,
                                   "req_id": None,
                                   "seq": svc.bank.next_op_seq()})
    svc.compact()
    th = threading.Thread(target=httpd.serve_forever, daemon=True,
                          name="bench-http")
    th.start()
    host, port = httpd.server_address[:2]
    return httpd, svc, th, f"http://{host}:{port}"


def warm_up(svc, cfg, plan) -> Dict[str, int]:
    """Run every program the window will run, at the window's shapes: one
    ask and its tells per study (each family's pick program,
    the fleet's fit and factors at the bucket), then a run of asks on the
    first GP-family study that holds every pending cap the traffic can
    reach, resolved as failed.  Journaled like the window's requests, so
    that a reopened copy redoes every fit from the seeded history on."""
    names = dep.study_names(cfg)
    n = int(cfg["ask_n"])
    obj = cfg["objective"]
    for name in names:
        for t in svc.ask(name, n=n, req_id=f"warm-{name}")["trials"]:
            svc.tell(name, t["id"], dep.objective(obj, t["params"]))
    gp = next(nm for nm, s in zip(names, dep.strategies(cfg))
              if s != "tpe")
    held = []
    for j in range(plan["pend_cap_max"] // n + 1):
        held += svc.ask(gp, n=n, req_id=f"warm-pend-{j}")["trials"]
    for t in held:
        svc.tell_failed(gp, t["id"])
    return {"asks": len(names) + plan["pend_cap_max"] // n + 1}


def bucket_of(svc, n: int) -> int:
    led = svc.bank.ledger
    k = int(led.n_observed().max())
    p = int(led.n_pending().max())
    return dep._pow2(k + max(4, -(-p // 4) * 4) + n)


# ------------------------------------------------------------------ spans
class TracedLock:
    """The service lock, with its acquisition written as a span."""

    def __init__(self, lock):
        self._lock = lock

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self._lock.release()

    def acquire(self, *a, **kw):
        import jax
        with jax.profiler.TraceAnnotation("bench.lock_wait"):
            return self._lock.acquire(*a, **kw)

    def release(self):
        self._lock.release()

    def _is_owned(self):
        return self._lock._is_owned()


def _spanned(name: str, fn):
    import jax

    @functools.wraps(fn)
    def inner(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return inner


def _pick_spanned(fn):
    """``StudyBank._pick_gp(cache, rows, fam, C, k_obs, k_pend, n, ...)``
    as the span ``bench.pick_gp``, carrying what the pick works on: the
    candidates ``S`` of ``d`` dims, the batch ``n``, and per study in the
    sub-batch its rows in the system (observations and pending,
    ``rows``), not the bucket's padded count."""
    import jax

    @functools.wraps(fn)
    def inner(*a, **kw):
        C, ko, kp, n = a[3], a[4], a[5], a[6]
        rows = ",".join(str(int(x) + int(y)) for x, y in zip(ko, kp))
        with jax.profiler.TraceAnnotation(
                "bench.pick_gp", rows=rows, S=int(C.shape[1]),
                d=int(C.shape[2]), n=int(n)):
            return fn(*a, **kw)
    return inner


def install_spans(svc) -> None:
    """Spans around the calls into each layer, written as profiler trace
    annotations so they share the device trace's clock: the service entry
    points and its lock, the journal append, the bank's obs stage and pick
    dispatches, and the candidate draw."""
    svc._lock = TracedLock(svc._lock)
    for verb in ("ask", "tell", "tell_failed"):
        setattr(svc, verb, _spanned(f"bench.{verb}", getattr(svc, verb)))
    svc.wal.append = _spanned("bench.journal", svc.wal.append)
    bank = svc.bank
    bank._pick_gp = _pick_spanned(bank._pick_gp)
    for m, name in (("_obs_stage", "bench.obs_stage"),
                    ("_dispatch_tpe", "bench.pick_tpe"),
                    ("_fit_if_due", "bench.fit")):
        setattr(bank, m, _spanned(name, getattr(bank, m)))
    spaces = {id(bank.space): bank.space}
    spaces.update({id(v.space): v.space for v in bank.studies})
    for sp in spaces.values():
        sp.sample_columns = _spanned("bench.sample_columns",
                                     sp.sample_columns)
        sp.encode_columns = _spanned("bench.encode_columns",
                                     sp.encode_columns)


# ---------------------------------------------------------------- capture
def copy_data_dir(src: str, dst: str) -> None:
    """A copy to reopen: the snapshot is linked (the service replaces it,
    never rewrites it), the journal and config are copied."""
    from repro.service.recovery import SNAPSHOT
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(dst)
    for f in os.listdir(src):
        if f == SNAPSHOT:
            os.link(os.path.join(src, f), os.path.join(dst, f))
        elif os.path.isfile(os.path.join(src, f)):
            shutil.copy2(os.path.join(src, f), os.path.join(dst, f))


def _ask_inputs(bank, view, n: int, cols, n_mc: int, out, seq: int,
                strategy_kwargs: Dict[str, Any]) -> Dict[str, Any]:
    """What one ask saw, read from the bank's ledger right after its pick
    (the obs stage has refit by then; the picks are not yet registered)."""
    led, b = bank.ledger, view._b
    fam = bank._fams[b]
    C = np.asarray(bank.space.encode_columns(cols, n_mc), np.float32)
    enc = np.asarray(out[1], np.float32)
    picks = []
    for row in enc:
        hit = np.nonzero((C == row[None, :]).all(1))[0]
        picks.append(int(hit[0]) if len(hit) else -1)
    obs = led.obs_ids(b)
    pend = led.pending_ids(b)
    ask = {"seq": seq, "study": b, "family": fam, "picks": picks,
           "C": C, "X": led.X[b, obs].copy(), "P": led.X[b, pend].copy(),
           "y": view.sign * led.y[b, obs],
           "n_obs_eff": len(obs) + len(pend),
           "domain_size": float(view.domain_size)}
    if fam == "tpe":
        ask["P"] = ask["P"][:0]
        ask["gamma"] = float(strategy_kwargs.get("gamma", 0.25))
        return ask
    # the service's hyperparameters and the count at its last fit, judged
    # against the reference's own fits; nothing else of the fit is taken
    ask.update(n_fit=int(led.n_fit[b]),
               theta=np.concatenate([led.log_ls[b], [led.log_var[b]],
                                     [led.log_noise[b]]]).astype(np.float64))
    if fam == "cluster":
        top_frac = float(strategy_kwargs.get("top_frac", 0.2))
        ask["n_top"] = min(max(n * 4, int(len(C) * top_frac)), len(C))
    return ask


def observations(svc, studies) -> Dict[int, tuple]:
    """Each study's observations in the order they were told, ``(X, y)``
    with the values signed as the study optimizes them."""
    led = svc.bank.ledger
    out = {}
    for b in studies:
        ids = led.obs_ids(int(b))
        out[int(b)] = (led.X[b, ids].astype(np.float64),
                       svc.bank.studies[b].sign
                       * led.y[b, ids].astype(np.float64))
    return out


@contextlib.contextmanager
def capture_asks(seqs) -> List[Dict[str, Any]]:
    """While open, every bank ask whose journal sequence number is in
    ``seqs`` is recorded (for a replay: the op being applied carries
    ``bank.op_seq + 1``)."""
    from repro.core.studybank import StudyBank
    orig = StudyBank.ask_view
    got: List[Dict[str, Any]] = []
    want = set(int(s) for s in seqs)

    def ask_view(self, view, n, cols, n_mc):
        seq = self.op_seq + 1
        out = orig(self, view, n, cols, n_mc)
        if seq in want:
            got.append(_ask_inputs(self, view, n, cols, n_mc, out, seq,
                                   self.strategy_kwargs))
        return out

    StudyBank.ask_view = ask_view
    try:
        yield got
    finally:
        StudyBank.ask_view = orig
