"""The program with one of its paths changed: the control, and the
planted faults that a run has to catch.

``changed(precision="default")`` traces the GP programs with the
platform's default products in place of the configured ``"highest"``
(the control: one bfloat16 pass on the TPU, the program's own lower
path);
each fault breaks the timed path underneath a run:

  * ``answer``: every pick program returns the next candidate's index;
  * ``state``: a tell is acknowledged and leaves the study unchanged;
  * ``journal``: the journal append writes nothing;
  * ``half``: an ask hands out half of the batch it was asked for.

Under ``precision``, JAX's in-memory caches are cleared on entry and exit,
so the changed programs trace and compile afresh and the sound ones come
back after.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

FAULTS = ("answer", "state", "journal", "half")


def _shift_picks(fn, n_cand_axis: int):
    def inner(*a, **kw):
        idx = fn(*a, **kw)
        return (idx + 1) % a[n_cand_axis].shape[1]
    return inner


@contextlib.contextmanager
def changed(fault: Optional[str] = None,
            precision: Optional[str] = None) -> Iterator[None]:
    import jax

    from repro.core import gp, tpe
    from repro.core.optimizer import AskTellOptimizer
    from repro.service.wal import WriteAheadLog

    undo = []

    def patch(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if precision is not None:
        real = jax.default_matmul_precision
        patch(jax, "default_matmul_precision",
              lambda _name, _real=real: _real(precision))
    if fault == "answer":
        patch(gp, "bank_pick", _shift_picks(gp.bank_pick, 0))
        patch(gp, "bank_cluster_pick", _shift_picks(gp.bank_cluster_pick, 0))
        patch(tpe, "fused_tpe_propose_bank",
              _shift_picks(tpe.fused_tpe_propose_bank, 2))
    elif fault == "state":
        def tell_once(self, trial_id, value):
            return self._trials[int(trial_id)], True
        patch(AskTellOptimizer, "tell_once", tell_once)
    elif fault == "journal":
        patch(WriteAheadLog, "append", lambda self, record, mid_hook=None:
              None)
    elif fault == "half":
        ask = AskTellOptimizer.ask
        patch(AskTellOptimizer, "ask",
              lambda self, n=1: ask(self, max(1, n // 2)))
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    if precision is not None:
        jax.clear_caches()
    try:
        yield
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)
        if precision is not None:
            jax.clear_caches()
