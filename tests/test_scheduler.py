import gc
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest
from scipy.stats import uniform

from repro.core import Tuner
from repro.core.async_tuner import AsyncTuner
from repro.scheduler import (BatchToAsyncAdapter, FaultInjection,
                             SerialScheduler, TaskQueueScheduler,
                             ThreadScheduler)

SPACE = {"x": uniform(0, 1)}
SRC = str(Path(__file__).resolve().parents[1] / "src")


def trial(p):
    return -(p["x"] - 0.5) ** 2


def test_serial_scheduler_drops_failures():
    def flaky(p):
        if p["x"] > 0.8:
            raise RuntimeError("boom")
        return trial(p)

    obj = SerialScheduler().make_objective(flaky)
    batch = [{"x": v} for v in (0.1, 0.9, 0.5, 0.95)]
    evals, params = obj(batch)
    assert len(evals) == 2
    assert all(p["x"] <= 0.8 for p in params)


def test_process_scheduler_spawns_workers(monkeypatch):
    """Workers start with ``spawn``: a forked child of a process that has
    initialised JAX would inherit its runtime and device handles."""
    import concurrent.futures as cf

    from repro.scheduler import ProcessScheduler

    methods = []
    real = cf.ProcessPoolExecutor

    def recording(*a, **kw):
        methods.append(kw["mp_context"].get_start_method())
        return real(*a, **kw)

    monkeypatch.setattr(cf, "ProcessPoolExecutor", recording)
    # ``len`` pickles by reference and needs no import in the child
    obj = ProcessScheduler(n_workers=2, timeout=60).make_objective(len)
    evals, params = obj([{"x": 0.1}, {"x": 0.2, "y": 1.0}])
    assert methods == ["spawn"]
    assert sorted(evals) == [1.0, 2.0]
    assert len(params) == 2


def test_thread_scheduler_straggler_deadline():
    def slow(p):
        if p["x"] > 0.5:
            time.sleep(5.0)  # straggler
        return trial(p)

    obj = ThreadScheduler(n_workers=4, timeout=0.5).make_objective(slow)
    t0 = time.time()
    evals, params = obj([{"x": v} for v in (0.1, 0.2, 0.9, 0.8)])
    assert time.time() - t0 < 2.0  # did not wait for stragglers
    assert len(evals) == 2


def test_thread_scheduler_straggler_does_not_block_exit():
    """Fault-semantics contract: a deadline-exceeding trial is *abandoned*.
    The seed implementation used ThreadPoolExecutor, whose non-daemon
    workers are joined at interpreter exit — a straggler held the whole
    process hostage for as long as it kept running."""
    code = """
        import sys, time
        sys.path.insert(0, %r)
        from repro.scheduler.local import ThreadScheduler

        def slow_or_fast(p):
            if p["slow"]:
                time.sleep(60.0)   # would block exit if joined
            return 1.0

        obj = ThreadScheduler(n_workers=2, timeout=0.3).make_objective(
            slow_or_fast)
        evals, params = obj([{"slow": True}, {"slow": False}])
        print("DONE", len(evals))
    """ % SRC
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=30)
    elapsed = time.monotonic() - t0
    assert out.returncode == 0, out.stderr
    assert "DONE 1" in out.stdout
    # the straggler sleeps 60s; a joined (non-daemon) thread would hold the
    # subprocess far past this bound
    assert elapsed < 15.0


def test_adapter_objective_cache_is_weak_and_per_object():
    """The adapter's objective cache must key on the fn *object*, not
    ``id(fn)``: ids are recycled after GC, so a fresh fn could silently
    inherit a stale objective, and id-keyed entries leak forever."""
    class CountingScheduler(SerialScheduler):
        def __init__(self):
            self.built = []

        def make_objective(self, trial_fn):
            self.built.append(trial_fn)
            return super().make_objective(trial_fn)

    sched = CountingScheduler()
    adapter = BatchToAsyncAdapter(sched)

    def make_fn(c):
        def fn(p):
            return c
        return fn

    f1 = make_fn(1.0)
    obj1 = adapter._objective_for(f1)[0]
    assert adapter._objective_for(f1)[0] is obj1   # cached per object
    assert len(sched.built) == 1
    del f1
    gc.collect()
    assert len(adapter._objectives) == 0           # no leak after GC
    # a new fn (possibly allocated at the recycled id) gets a *fresh*
    # objective, never the stale one
    f2 = make_fn(2.0)
    obj2 = adapter._objective_for(f2)[0]
    assert obj2 is not obj1
    assert len(sched.built) == 2
    assert obj2([{"x": 0.0}])[0] == [2.0]
    # unhashable callables fall back to per-call objectives, uncached
    class UnhashableFn:
        __hash__ = None

        def __call__(self, p):
            return 3.0

    u = UnhashableFn()
    assert adapter._objective_for(u)[0]([{"x": 0.0}])[0] == [3.0]
    assert len(adapter._objectives) == 1           # only f2 cached


def test_adapter_pins_wrapped_fn_for_equal_bound_methods():
    """Bound methods are equal-but-distinct objects per access: a cache hit
    wraps the *first* object, so the caller must pin that one — otherwise
    it can be GC'd while the reusing trial is still in flight and the
    trial spuriously fails."""
    class Trialer:
        def trial(self, p):
            return float(p["x"])

    t = Trialer()
    adapter = BatchToAsyncAdapter(SerialScheduler())
    m1 = t.trial
    obj1, pin1 = adapter._objective_for(m1)
    m2 = t.trial
    assert m2 is not m1 and m2 == m1
    obj2, pin2 = adapter._objective_for(m2)
    assert obj2 is obj1          # equality hit reuses the objective...
    assert pin2 is m1            # ...and pins the object it actually wraps
    # end-to-end: churning bound methods across submits never goes stale
    handles = [adapter.submit(t.trial, {"x": float(i)}) for i in range(4)]
    gc.collect()
    while not all(h.done.is_set() for h in handles):
        adapter.wait_any(handles, timeout=5.0)
    assert [h.error for h in handles] == [None] * 4
    assert sorted(h.result for h in handles) == [0.0, 1.0, 2.0, 3.0]


def test_thread_scheduler_deadline_cancels_unstarted_trials():
    """Trials still queued behind the worker gate when the deadline fires
    must never start (the old executor cancelled its unstarted futures;
    the daemon rewrite must not regress into running the whole backlog on
    abandoned threads)."""
    import threading as th

    started = []
    lock = th.Lock()

    def slow(p):
        with lock:
            started.append(p["i"])
        time.sleep(0.8)
        return 1.0

    obj = ThreadScheduler(n_workers=2, timeout=0.3).make_objective(slow)
    evals, _ = obj([{"i": k} for k in range(12)])
    assert evals == []            # nothing finishes inside the deadline
    time.sleep(1.5)               # give any buggy backlog time to run
    with lock:
        assert len(started) <= 4  # only in-flight waves, never the backlog


def test_taskqueue_submit_after_shutdown_raises():
    """submit() after shutdown() used to enqueue into a dead queue (start()
    no-ops once _started is set) and wait_any hung until timeout."""
    sched = TaskQueueScheduler(n_workers=2)
    h = sched.submit(trial, {"x": 0.4})
    assert sched.wait_any([h], timeout=5.0) == [h]
    sched.shutdown()
    with pytest.raises(RuntimeError, match="shutdown"):
        sched.submit(trial, {"x": 0.5})


def test_taskqueue_stats_consistent_under_worker_races():
    """Counter increments run under the scheduler lock: completed+failed
    must exactly equal the number of finished tasks."""
    sched = TaskQueueScheduler(
        n_workers=8, max_retries=1,
        faults=FaultInjection(failure_rate=0.3, seed=3))
    tasks = [sched.submit(trial, {"x": v})
             for v in np.linspace(0, 1, 64)]
    evals, _ = sched.gather(tasks, timeout=30.0)
    assert all(t.done.is_set() for t in tasks)
    assert sched.stats["completed"] + sched.stats["failed"] == 64
    assert sched.stats["completed"] == len(evals)
    sched.shutdown()


def test_taskqueue_fault_injection_and_retry():
    sched = TaskQueueScheduler(
        n_workers=4, timeout=2.0, max_retries=2,
        faults=FaultInjection(failure_rate=0.5, seed=7))
    obj = sched.make_objective(trial)
    evals, params = obj([{"x": v} for v in np.linspace(0, 1, 12)])
    # with 2 retries at 50% failure, nearly all should eventually land
    assert len(evals) >= 8
    assert sched.stats["retried"] > 0
    sched.shutdown()


@pytest.mark.parametrize("straggler_rate", [0.0, 0.4])
def test_taskqueue_fault_injection_is_deterministic(straggler_rate):
    """Injected failures are a pure function of (faults.seed, submit
    order): two runs at failure_rate=0.5 must drop *identical* task sets
    even though the queue races tasks across 8 worker threads (the old
    shared ``random.Random`` let thread scheduling decide which tasks
    died)."""
    def run():
        sched = TaskQueueScheduler(
            n_workers=8,
            faults=FaultInjection(failure_rate=0.5, seed=13,
                                  straggler_rate=straggler_rate,
                                  straggler_delay=0.01))
        batch = [{"x": round(v, 6)} for v in np.linspace(0, 1, 40)]
        tasks = [sched.submit(trial, p) for p in batch]
        sched.gather(tasks, timeout=30.0)
        dropped = frozenset(t.params["x"] for t in tasks
                            if t.error is not None)
        sched.shutdown()
        return dropped

    first = run()
    assert 0 < len(first) < 40        # the injection actually fired
    for _ in range(2):
        assert run() == first


def test_taskqueue_fault_determinism_unaffected_by_retry_races():
    """Retries draw from the failed task's own RNG stream, so the final
    survivor set stays deterministic under max_retries too."""
    def run():
        sched = TaskQueueScheduler(
            n_workers=6, max_retries=1,
            faults=FaultInjection(failure_rate=0.5, seed=5))
        tasks = [sched.submit(trial, {"x": round(v, 6)})
                 for v in np.linspace(0, 1, 32)]
        sched.gather(tasks, timeout=30.0)
        dropped = frozenset(t.params["x"] for t in tasks
                            if t.error is not None)
        sched.shutdown()
        return dropped

    assert run() == run()


def test_taskqueue_no_faults_full_batch():
    sched = TaskQueueScheduler(n_workers=2)
    evals, params = sched.make_objective(trial)(
        [{"x": v} for v in (0.1, 0.5, 0.9)])
    assert len(evals) == 3
    sched.shutdown()


def test_end_to_end_tuning_under_faults():
    sched = TaskQueueScheduler(
        n_workers=4, timeout=1.0, max_retries=1,
        faults=FaultInjection(failure_rate=0.25, straggler_rate=0.15,
                              straggler_delay=3.0, seed=11))
    res = Tuner(SPACE, sched.make_objective(trial),
                dict(optimizer="bayesian", batch_size=4, num_iteration=6,
                     seed=0, mc_samples=1000, fit_steps=10)).maximize()
    assert res.best_objective > -0.01
    assert res.n_failed > 0  # faults actually happened
    sched.shutdown()


def test_async_tuner_continuous_batching():
    sched = TaskQueueScheduler(n_workers=4)
    res = AsyncTuner(SPACE, trial, sched, num_evals=12, batch_size=4,
                     seed=0, mc_samples=800).maximize()
    assert len(res["objective_values"]) == 12
    assert res["best_objective"] > -0.05
    sched.shutdown()


# --------------------------------------------------------------------------- #
# Coalescing adapter: per-batch setup cost amortization
# --------------------------------------------------------------------------- #
class CountingScheduler(SerialScheduler):
    """ProcessScheduler-shaped: every objective call pays one 'pool setup'
    (here just counted), so dispatch count == setup count."""

    def __init__(self):
        import threading
        self.dispatches = []
        self.entered = threading.Event()
        self.release = threading.Event()

    def make_objective(self, trial_fn):
        inner = super().make_objective(trial_fn)

        def objective(params_list):
            self.dispatches.append(len(params_list))
            if len(self.dispatches) == 1:
                self.entered.set()
                self.release.wait(10)
            return inner(params_list)

        return objective


def test_batch_to_async_adapter_coalesces_queued_submits():
    """Submits queued while a dispatch is in flight ride ONE later
    objective call: 8 single-trial submits cost 2 scheduler dispatches
    (1 + the 7 that queued behind it), amortizing per-batch setup cost."""
    sched = CountingScheduler()
    adapter = sched.as_async(coalesce=True)
    h0 = adapter.submit(trial, {"x": 0.125})
    assert sched.entered.wait(10)
    later = [adapter.submit(trial, {"x": i / 16.0}) for i in range(1, 8)]
    sched.release.set()
    for h in [h0] + later:
        assert h.done.wait(10)
        assert h.error is None
        assert h.result == pytest.approx(trial(h.params))
    assert sched.dispatches == [1, 7]


def test_batch_to_async_adapter_default_stays_per_trial():
    sched = CountingScheduler()
    sched.release.set()   # don't block the first dispatch
    adapter = sched.as_async()
    handles = [adapter.submit(trial, {"x": i / 8.0}) for i in range(4)]
    for h in handles:
        assert h.done.wait(10)
    assert sorted(sched.dispatches) == [1, 1, 1, 1]


def test_coalescing_adapter_keeps_fault_semantics():
    """A trial dropped inside a coalesced batch surfaces as a failed
    handle; its batchmates still complete."""
    import threading

    class HalfDrop(SerialScheduler):
        def __init__(self):
            self.entered = threading.Event()
            self.release = threading.Event()
            self.calls = 0

        def make_objective(self, trial_fn):
            inner = super().make_objective(trial_fn)

            def objective(params_list):
                self.calls += 1
                if self.calls == 1:
                    self.entered.set()
                    self.release.wait(10)
                return inner(params_list)

            return objective

    def flaky(p):
        if p["x"] > 0.5:
            raise RuntimeError("boom")
        return trial(p)

    sched = HalfDrop()
    adapter = sched.as_async(coalesce=True)
    first = adapter.submit(flaky, {"x": 0.1})
    assert sched.entered.wait(10)
    ok = adapter.submit(flaky, {"x": 0.2})
    bad = adapter.submit(flaky, {"x": 0.9})
    sched.release.set()
    for h in (first, ok, bad):
        assert h.done.wait(10)
    assert first.error is None and ok.error is None
    assert bad.result is None and isinstance(bad.error, RuntimeError)


# --------------------------------------------------------------------------- #
# graceful drain shutdown
# --------------------------------------------------------------------------- #
def test_task_queue_shutdown_drains_in_flight():
    """shutdown(timeout=) lets queued work finish before stopping the
    workers, and refuses new submits while draining."""
    sched = TaskQueueScheduler(n_workers=2)
    release = __import__("threading").Event()

    def slowish(p):
        release.wait(10)
        return trial(p)

    handles = [sched.submit(slowish, {"x": 0.1 * i}) for i in range(4)]
    drainer = {}

    def do_drain():
        drainer["drained"] = sched.shutdown(timeout=10.0)

    t = __import__("threading").Thread(target=do_drain)
    t.start()
    time.sleep(0.05)          # drain has started: submits must be refused
    with pytest.raises(RuntimeError, match="drain"):
        sched.submit(slowish, {"x": 0.9})
    release.set()
    t.join(10)
    assert drainer["drained"] is True
    assert all(h.done.is_set() and h.error is None for h in handles)


def test_task_queue_shutdown_timeout_reports_undrained():
    sched = TaskQueueScheduler(n_workers=1)
    sched.submit(lambda p: time.sleep(5) or 0.0, {"x": 0.5})
    assert sched.shutdown(timeout=0.1) is False


def test_batch_adapter_shutdown_drains_and_refuses_submits():
    release = __import__("threading").Event()

    def gated(p):
        release.wait(10)
        return trial(p)

    adapter = BatchToAsyncAdapter(SerialScheduler())
    handles = [adapter.submit(gated, {"x": 0.2}) for _ in range(3)]
    out = {}
    t = __import__("threading").Thread(
        target=lambda: out.update(d=adapter.shutdown(timeout=10.0)))
    t.start()
    time.sleep(0.05)
    with pytest.raises(RuntimeError, match="shutdown"):
        adapter.submit(gated, {"x": 0.3})      # submit-during-drain
    release.set()
    t.join(10)
    assert out["d"] is True
    assert all(h.done.is_set() for h in handles)
    # already-drained second call is a cheap no-op
    assert adapter.shutdown() is True


def test_batch_adapter_submit_shutdown_race_cannot_orphan():
    """submit's closed-check and outstanding-increment are one critical
    section under the adapter lock: a submit racing shutdown(timeout) is
    either counted by the drain or refused.  drained=True therefore
    guarantees every accepted trial completed — the contract the durable
    service snapshots on."""
    import threading
    for _ in range(25):
        adapter = BatchToAsyncAdapter(SerialScheduler())
        accepted = []
        barrier = threading.Barrier(2)

        def spam(adapter=adapter, accepted=accepted, barrier=barrier):
            barrier.wait()
            for i in range(100):
                try:
                    accepted.append(adapter.submit(trial, {"x": 0.01 * i}))
                except RuntimeError:
                    return

        t = threading.Thread(target=spam)
        t.start()
        barrier.wait()
        assert adapter.shutdown(timeout=10.0) is True
        t.join(10)
        assert all(h.done.is_set() for h in accepted)


def test_task_queue_submit_shutdown_race_cannot_orphan():
    """Same contract for TaskQueueScheduler: the drain check and the
    outstanding increment share the completion cv, so a drained=True
    can't leave a racing submit's task in the queue."""
    import threading
    for _ in range(10):
        sched = TaskQueueScheduler(n_workers=2)
        accepted = []
        barrier = threading.Barrier(2)

        def spam(sched=sched, accepted=accepted, barrier=barrier):
            barrier.wait()
            for i in range(100):
                try:
                    accepted.append(sched.submit(trial, {"x": 0.01 * i}))
                except RuntimeError:
                    return

        t = threading.Thread(target=spam)
        t.start()
        barrier.wait()
        assert sched.shutdown(timeout=10.0) is True
        t.join(10)
        assert all(h.done.is_set() for h in accepted)
