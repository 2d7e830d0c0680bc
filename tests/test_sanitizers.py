"""Runtime sanitizers: retrace audits, transfer guards, lock assertions,
and the steady-state serving contract they gate end to end."""
import importlib.util
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats

from repro.analysis.sanitizers import (RetraceError, assert_holds,
                                       debug_locks_enabled, no_retrace,
                                       no_transfer, set_debug_locks)
from repro.core import StudyBank

SPACE = {"x": stats.uniform(0, 1), "y": stats.uniform(-1, 2)}


def _objective(p):
    return -(p["x"] - 0.3) ** 2 - (p["y"] - 0.5) ** 2


def _drive(bank, rounds):
    for _ in range(rounds):
        for b, ts in enumerate(bank.ask_all(1)):
            for t in ts:
                bank.tell(b, t.id, _objective(t.params))


# --------------------------------------------------------------------------- #
# no_retrace
# --------------------------------------------------------------------------- #
def test_no_retrace_clean_on_cache_hits():
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4))  # warm
    with no_retrace({"f": f}) as rep:
        f(jnp.ones(4))
        f(jnp.ones(4))
    assert rep.violations == 0
    assert rep.deltas == {"f": 0}
    assert rep.detail() == ""


def test_no_retrace_raises_on_new_shape():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4))
    with pytest.raises(RetraceError, match="bad_entry=1/0"):
        with no_retrace({"bad_entry": f}):
            f(jnp.ones(8))  # new shape -> new compile


def test_no_retrace_expected_budget_allows_known_compiles():
    f = jax.jit(lambda x: x - 1)
    f(jnp.ones(4))
    with no_retrace({"f": f}, expected={"f": 1}) as rep:
        f(jnp.ones(8))
        f(jnp.ones(8))  # second call is a hit
    assert rep.violations == 0
    assert rep.deltas == {"f": 1}


def test_no_retrace_report_mode_fills_expected_late():
    """The benchmark idiom: audit with raise_on_violation=False, assign
    rep.expected once the sweep knows its bucket count."""
    f = jax.jit(lambda x: x / 2)
    f(jnp.ones(4))
    with no_retrace({"f": f}, raise_on_violation=False) as rep:
        f(jnp.ones(16))
        rep.expected = {"f": 1}
    assert rep.violations == 0
    with no_retrace({"f": f}, raise_on_violation=False) as rep:
        f(jnp.ones(32))
    assert rep.violations == 1
    assert rep.detail() == "f=1/0"


# --------------------------------------------------------------------------- #
# no_transfer
# --------------------------------------------------------------------------- #
def test_no_transfer_implicit_h2d_raises_explicit_allowed():
    x = np.ones(3, np.float32)
    with no_transfer(device_to_host=None, host_to_device="disallow"):
        jnp.asarray(x)  # explicit upload: always sanctioned
        with pytest.raises(Exception, match="[Dd]isallow"):
            jnp.sin(x)  # implicit operand upload


def test_no_transfer_all_threads_guards_other_threads():
    """A server's handler threads run the device work: the thread-local
    guard misses them, the process-wide one does not, and the previous
    level comes back after the block."""
    x = np.ones(3, np.float32)
    errors = []

    def work():
        try:
            jnp.sin(x)  # implicit operand upload
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def in_thread():
        t = threading.Thread(target=work)
        t.start()
        t.join()

    with no_transfer(device_to_host=None, host_to_device="disallow"):
        in_thread()
    assert errors == []
    with no_transfer(device_to_host=None, host_to_device="disallow",
                     all_threads=True):
        in_thread()
    assert len(errors) == 1 and "isallow" in str(errors[0])
    in_thread()
    assert len(errors) == 1


def test_no_transfer_default_keeps_device_get_and_uploads_open():
    y = jax.jit(lambda v: v + 1)(jnp.ones(3))
    jax.block_until_ready(y)
    with no_transfer():
        jnp.asarray(np.ones(3, np.float32))    # designed h2d traffic
        out = jax.device_get(y)                # the sanctioned exit
    np.testing.assert_allclose(out, 2.0)


# --------------------------------------------------------------------------- #
# assert_holds
# --------------------------------------------------------------------------- #
def test_assert_holds_noop_when_disabled():
    prev = set_debug_locks(False)
    try:
        assert_holds(threading.RLock())  # not held: still no raise
    finally:
        set_debug_locks(prev)


def test_assert_holds_checks_ownership_when_enabled():
    prev = set_debug_locks(True)
    try:
        assert debug_locks_enabled()
        rlock = threading.RLock()
        with pytest.raises(AssertionError, match="not held"):
            assert_holds(rlock)
        with rlock:
            assert_holds(rlock)
        cv = threading.Condition()
        with pytest.raises(AssertionError):
            assert_holds(cv)
        with cv:
            assert_holds(cv)
        plain = threading.Lock()
        with pytest.raises(AssertionError):
            assert_holds(plain)
        with plain:
            assert_holds(plain)
    finally:
        set_debug_locks(prev)


def test_scheduler_drain_contracts_pass_under_debug_locks():
    """The adopted assert_holds sites (shutdown drain predicates) hold
    their declared locks on the real paths."""
    from repro.scheduler import SerialScheduler
    from repro.scheduler.base import BatchToAsyncAdapter
    from repro.scheduler.distributed import TaskQueueScheduler

    prev = set_debug_locks(True)
    try:
        adapter = BatchToAsyncAdapter(SerialScheduler())
        h = adapter.submit(lambda p: p["x"], {"x": 1.5})
        adapter.wait_any([h], timeout=10.0)
        assert adapter.shutdown(timeout=10.0)

        q = TaskQueueScheduler(n_workers=2)
        hs = [q.submit(lambda p: p["x"], {"x": i}) for i in range(3)]
        q.wait_any(hs, timeout=10.0)
        assert q.shutdown(timeout=10.0)
    finally:
        set_debug_locks(prev)


# --------------------------------------------------------------------------- #
# steady-state serving under both sanitizers (the PR 4/6 contract)
# --------------------------------------------------------------------------- #
def test_steady_state_bank_serving_is_sanitizer_clean():
    """Warm StudyBank ask_all/tell rounds inside one shape bucket: not a
    single jit compile of any BANK_JITS entry point and no implicit
    transfers, with real tells (growing n_obs) in the loop."""
    bank = StudyBank(SPACE, 4, optimizer="bayesian", seed=0, mc_samples=32)
    _drive(bank, 3)  # warmup: GP pipeline + first hyper fit compile here
    with no_transfer(), no_retrace() as rep:
        _drive(bank, 5)
    assert rep.violations == 0, rep.detail()


def test_smoke_module_passes():
    from repro.analysis import smoke
    assert smoke.run(rounds=4, verbose=False) == 0


class _FreshJit:
    """Deliberately broken jit wrapper: re-jits the wrapped function on
    every call, so each invocation is a fresh compile."""

    def __init__(self, jitted):
        self._inner = jitted.__wrapped__
        self._jits = []

    def __call__(self, *args, **kwargs):
        j = jax.jit(self._inner)
        self._jits.append(j)
        return j(*args, **kwargs)

    def _cache_size(self):
        return sum(j._cache_size() for j in self._jits)


def test_injected_retrace_trips_the_gate(monkeypatch):
    """Negative control: break bank_exp's caching and the zero-retrace
    audit must report violations (the bench gate then exits 1)."""
    from repro.core import gp as gp_lib

    bank = StudyBank(SPACE, 2, optimizer="bayesian", seed=3, mc_samples=32)
    _drive(bank, 3)  # warm with the intact pipeline
    fresh = _FreshJit(gp_lib.bank_exp)
    monkeypatch.setattr(gp_lib, "bank_exp", fresh)
    monkeypatch.setitem(gp_lib.BANK_JITS, "bank_exp", fresh)
    with no_retrace(raise_on_violation=False) as rep:
        _drive(bank, 2)
    assert rep.violations >= 2  # one fresh compile per audited ask
    assert "bank_exp" in rep.detail()


# --------------------------------------------------------------------------- #
# the benchmark gate plumbing
# --------------------------------------------------------------------------- #
def _load_multi_study():
    path = Path(__file__).resolve().parents[1] / "benchmarks" \
        / "multi_study.py"
    spec = importlib.util.spec_from_file_location("_multi_study_bench",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_multi_study_main_exits_nonzero_on_retraces(monkeypatch):
    mod = _load_multi_study()
    import repro.compile_cache
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(mod, "run_throughput", lambda **kw: [])
    monkeypatch.setattr(mod, "run_retrace_sweep", lambda **kw: 3)
    monkeypatch.setattr(sys, "argv", ["multi_study.py", "--quick"])
    with pytest.raises(SystemExit) as exc:
        mod.main()
    assert exc.value.code == 1
    monkeypatch.setattr(mod, "run_retrace_sweep", lambda **kw: 0)
    mod.main()  # zero retraces: returns without SystemExit
