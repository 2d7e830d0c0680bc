"""Local schedulers: serial (paper Listing 3), thread pool, process pool.

All three implement the batch-objective protocol; ``.as_async()`` (from
``BatchSchedulerBase``) returns the submit/wait_any view so they can also
drive ``AsyncTuner``'s completion-event loop.
"""
from __future__ import annotations

import concurrent.futures as cf
import logging
import multiprocessing
import threading
import time
from typing import Any, Dict, List, Optional

from repro.scheduler.base import BatchSchedulerBase, Objective, TrialFn

_log = logging.getLogger(__name__)


class SerialScheduler(BatchSchedulerBase):
    """Sequential evaluation; failed trials are dropped (partial results)."""

    def make_objective(self, trial_fn: TrialFn) -> Objective:
        def objective(params_list):
            evals, params = [], []
            for par in params_list:
                try:
                    evals.append(float(trial_fn(par)))
                    params.append(par)
                except Exception as e:
                    # dropped -> tuner never observes it (paper's
                    # fault-tolerance contract), but the drop is visible
                    _log.debug("trial dropped (%s): %r", par, e)
            return evals, params

        return objective


class ThreadScheduler(BatchSchedulerBase):
    """Threaded evaluation with a per-batch deadline.

    Results that miss the deadline (stragglers) are NOT waited for — the
    batch returns partially, exactly the paper's out-of-order/missing-results
    contract.  Trials run on *daemon* threads gated by a semaphore (at most
    ``n_workers`` concurrent), so an abandoned straggler can never block
    interpreter exit.  (``concurrent.futures.ThreadPoolExecutor`` workers
    are non-daemon and joined at interpreter shutdown — one straggler past
    the deadline would stall the whole process for as long as it runs.)
    """

    def __init__(self, n_workers: int = 4, timeout: Optional[float] = None):
        self.n_workers = n_workers
        self.timeout = timeout

    def make_objective(self, trial_fn: TrialFn) -> Objective:
        def objective(params_list):
            cv = threading.Condition()
            gate = threading.BoundedSemaphore(self.n_workers)
            cancelled = threading.Event()
            evals: List[float] = []
            params: List[Dict[str, Any]] = []
            state = {"left": len(params_list)}

            def run(par):
                try:
                    with gate:
                        # deadline already fired while queued behind the
                        # gate: never start the trial (matches the old
                        # executor's cancel_futures semantics — only
                        # already-*running* trials are abandoned mid-air)
                        if cancelled.is_set():
                            return
                        v = float(trial_fn(par))
                    with cv:
                        evals.append(v)
                        params.append(par)
                except Exception as e:
                    # dropped -> tuner never observes it, but visibly
                    _log.debug("trial dropped (%s): %r", par, e)
                finally:
                    with cv:
                        state["left"] -= 1
                        cv.notify_all()

            for par in params_list:
                threading.Thread(target=run, args=(par,), daemon=True,
                                 name="mango-thread-worker").start()
            deadline = (None if self.timeout is None
                        else time.monotonic() + self.timeout)
            with cv:
                while state["left"] > 0:
                    rem = (None if deadline is None
                           else deadline - time.monotonic())
                    if rem is not None and rem <= 0:
                        break  # deadline: return what we have
                    cv.wait(rem)
                # snapshot under the lock: a straggler landing after the
                # deadline appends to the dead lists, not the result
                out = (list(evals), list(params))
            cancelled.set()
            return out

        return objective


class ProcessScheduler(BatchSchedulerBase):
    """Process-pool evaluation (trial_fn must be picklable).

    Workers are started with ``spawn``, never ``fork``: a fork of a
    process that has initialised JAX copies its runtime threads and device
    handles into the child.  A trial in a child process cannot use the
    chip either way: the tuner's process holds it, and a chip belongs to
    one process at a time.  Objectives that run on the chip use
    ``ThreadScheduler`` or ``SerialScheduler`` in the tuner's process.
    """

    def __init__(self, n_workers: int = 2, timeout: Optional[float] = None):
        self.n_workers = n_workers
        self.timeout = timeout

    def make_objective(self, trial_fn: TrialFn) -> Objective:
        def objective(params_list):
            evals, params = [], []
            with cf.ProcessPoolExecutor(
                    max_workers=self.n_workers,
                    mp_context=multiprocessing.get_context("spawn")) as ex:
                futs = {ex.submit(trial_fn, par): par for par in params_list}
                try:
                    for fut in cf.as_completed(futs, timeout=self.timeout):
                        par = futs[fut]
                        try:
                            evals.append(float(fut.result()))
                            params.append(par)
                        except Exception as e:
                            # dropped -> tuner never observes it
                            _log.debug("trial dropped (%s): %r", par, e)
                except cf.TimeoutError:
                    for fut in futs:
                        fut.cancel()
            return evals, params

        return objective
