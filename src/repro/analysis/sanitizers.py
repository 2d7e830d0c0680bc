"""Runtime sanitizers: enforce device-hygiene invariants while code runs.

Three guards, each wrapping an invariant that is CI-gated elsewhere in
this repo:

  * ``no_retrace()`` — generalizes the jit-cache-delta audit that
    ``benchmarks/multi_study.py`` pioneered for the PR 6 zero-retrace
    contract into a reusable context manager over any mapping of named
    jitted entry points.
  * ``no_transfer()`` — wraps ``jax.transfer_guard_*`` for steady-state
    ask paths.  By default only *implicit device->host* transfers are
    disallowed: those are the hidden syncs (``.item()``, ``float()``,
    ``np.asarray`` on a device value) that stall the dispatch pipeline,
    while the candidate upload each ask is a designed host->device
    transfer (4 per ask, measured in PR 4).  Explicit
    ``jax.device_get()`` stays allowed — it marks the one deliberate
    exit point.
  * ``assert_holds(lock)`` — debug-mode lock-ownership assertion for
    caller-must-hold functions (the PR 3/7 bug class).  Free when
    disabled; enable with ``REPRO_DEBUG_LOCKS=1`` or ``set_debug_locks``.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Mapping, Optional


class RetraceError(AssertionError):
    """A jitted entry point compiled more often than its budget."""


class RetraceReport:
    """Mutable report yielded by ``no_retrace``.

    ``expected`` maps entry-point name -> compiles the audited region is
    *allowed* (default 0 for every name: pure steady state).  Callers
    that legitimately cross shape buckets (the multi-study growth sweep)
    fill it in before the block exits.  After exit, ``deltas`` holds the
    per-entry-point new-cache-entry counts and ``violations`` the summed
    excess ``max(0, delta - expected)``.
    """

    def __init__(self, jits: Mapping[str, object],
                 expected: Optional[Mapping[str, int]] = None):
        self.jits = dict(jits)
        self.expected: Dict[str, int] = dict(expected or {})
        self.base: Dict[str, int] = {}
        self.deltas: Dict[str, int] = {}
        self.violations: int = 0
        self._finished = False

    def _snapshot(self) -> Dict[str, int]:
        return {name: int(f._cache_size())
                for name, f in self.jits.items()}

    def finish(self) -> None:
        now = self._snapshot()
        self.deltas = {k: now[k] - self.base[k] for k in self.jits}
        self.violations = sum(
            max(0, self.deltas[k] - int(self.expected.get(k, 0)))
            for k in self.jits)
        self._finished = True

    def detail(self) -> str:
        """`name=delta/expected` for every mismatching entry point."""
        return ",".join(
            f"{k}={self.deltas[k]}/{int(self.expected.get(k, 0))}"
            for k in sorted(self.jits)
            if self.deltas.get(k, 0) != int(self.expected.get(k, 0)))


@contextlib.contextmanager
def no_retrace(jits: Optional[Mapping[str, object]] = None,
               expected: Optional[Mapping[str, int]] = None,
               raise_on_violation: bool = True):
    """Audit the jit caches of ``jits`` (name -> jitted callable) across
    the block: every entry point may add at most ``expected[name]``
    (default 0) cache entries, i.e. compile at most that many times.

    ``jits=None`` audits the bank serving pipeline (``gp.BANK_JITS``) —
    the PR 6 zero-retrace contract.  Yields a ``RetraceReport``; with
    ``raise_on_violation=False`` the caller inspects
    ``report.violations`` itself (the benchmark gate turns it into a
    nonzero exit code instead of a traceback).
    """
    if jits is None:
        from repro.core import gp as gp_lib
        jits = gp_lib.BANK_JITS
    rep = RetraceReport(jits, expected)
    rep.base = rep._snapshot()
    try:
        yield rep
    finally:
        rep.finish()
    if raise_on_violation and rep.violations:
        raise RetraceError(
            f"{rep.violations} unexpected jit compile(s) in audited "
            f"region: {rep.detail()} (name=new_entries/expected) — a "
            "retrace leaked into the steady state")


@contextlib.contextmanager
def no_transfer(device_to_host: Optional[str] = "disallow",
                host_to_device: Optional[str] = None,
                device_to_device: Optional[str] = None,
                all_threads: bool = False):
    """Transfer-guard the block.  Levels per direction: None (leave
    unchanged), "allow", "log", "disallow", "log_explicit",
    "disallow_explicit" — see ``jax.transfer_guard``.

    The default guards only implicit device->host transfers: that is the
    hidden-sync class (REPRO-J101) the fused ask paths must never pay,
    while candidate uploads are designed host->device traffic and
    ``jax.device_get`` remains the sanctioned exit.  Pass
    ``host_to_device="disallow"`` too when auditing a fully
    device-resident region.

    JAX's guard contexts hold for the calling thread only.  With
    ``all_threads=True`` the levels are set process-wide for the block
    instead (and restored after), which is what auditing a server whose
    handler threads run the device work needs.

    Backend caveat: on the CPU backend device buffers live in host
    memory, so device->host reads are zero-copy and the d2h guard can
    never fire — it becomes load-bearing on accelerator backends.  The
    host->device direction enforces on every backend (the sanitizer
    tests pin the implicit-raises / explicit-allowed split there).
    """
    import jax
    levels = {"jax_transfer_guard_device_to_host": device_to_host,
              "jax_transfer_guard_host_to_device": host_to_device,
              "jax_transfer_guard_device_to_device": device_to_device}
    levels = {k: v for k, v in levels.items() if v is not None}
    if all_threads:
        prev = {k: getattr(jax.config, k) for k in levels}
        try:
            for k, v in levels.items():
                jax.config.update(k, v)
            yield
        finally:
            for k, v in prev.items():
                jax.config.update(k, v)
        return
    guards = {"jax_transfer_guard_device_to_host":
              jax.transfer_guard_device_to_host,
              "jax_transfer_guard_host_to_device":
              jax.transfer_guard_host_to_device,
              "jax_transfer_guard_device_to_device":
              jax.transfer_guard_device_to_device}
    with contextlib.ExitStack() as stack:
        for k, v in levels.items():
            stack.enter_context(guards[k](v))
        yield


# --------------------------------------------------------------------- locks
_DEBUG_LOCKS = os.environ.get("REPRO_DEBUG_LOCKS", "") not in ("", "0")


def set_debug_locks(enabled: bool) -> bool:
    """Toggle ``assert_holds`` enforcement; returns the previous value."""
    global _DEBUG_LOCKS
    prev, _DEBUG_LOCKS = _DEBUG_LOCKS, bool(enabled)
    return prev


def debug_locks_enabled() -> bool:
    return _DEBUG_LOCKS


def assert_holds(lock) -> None:
    """Assert the calling thread holds ``lock``.

    A no-op unless debug mode is on (``REPRO_DEBUG_LOCKS=1`` or
    ``set_debug_locks(True)``), so caller-must-hold contracts — the
    commit path of the service, the drain predicates of the schedulers —
    can declare themselves at zero steady-state cost.  RLock/Condition
    check true ownership (``_is_owned``); a plain ``threading.Lock``
    has no owner, so only held-by-someone (``locked()``) is checkable.
    The lint rule REPRO-C201 treats a declared ``assert_holds(self.X)``
    as lock-held evidence for the whole function.
    """
    if not _DEBUG_LOCKS:
        return
    owned = getattr(lock, "_is_owned", None)
    if owned is not None:
        if not owned():
            raise AssertionError(
                f"assert_holds: {lock!r} is not held by "
                f"{threading.current_thread().name}")
        return
    locked = getattr(lock, "locked", None)
    if locked is not None and not locked():
        raise AssertionError(
            f"assert_holds: {lock!r} is not held (plain Lock: ownership "
            "is unverifiable, only held-by-someone)")
