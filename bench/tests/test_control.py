"""The control that each correctness limit is held against, at a size a
test run holds: the GP programs traced at ``"default"`` precision in
place of the configured ``"highest"``, and the TPE reference computed in
bfloat16.
On the chip both read far above the limits (``PERF.md``); here the test
shows that the control changes what it has to change, and only for as long
as it is on."""
import numpy as np
import pytest

from bench.lib import reference as ref
from bench.lib.faults import changed


def _factors_hlo():
    import jax.numpy as jnp

    from repro.core import gp
    args = (jnp.zeros((1, 16, 3)), jnp.ones((1, 16)), jnp.ones((1, 3)),
            jnp.ones((1,)), jnp.full((1,), 0.1))
    return gp.bank_factors.lower(*args).as_text()


def test_control_traces_the_gp_programs_at_default():
    sound = _factors_hlo()
    assert "HIGHEST" in sound
    with changed(precision="default"):
        ctl = _factors_hlo()
    assert "HIGHEST" not in ctl and "DEFAULT" in ctl
    assert _factors_hlo() == sound


def _tpe_ask(seed, n=300, S=4000, d=12):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = -((X - 0.3) ** 2).sum(1) + rng.normal(0.0, 0.01, n)
    C = rng.random((S, d))
    picks = np.argsort(-ref.tpe_scores(X, y, C, 0.25))[:4].tolist()
    return {"X": X, "y": y, "C": C, "gamma": 0.25, "picks": picks}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tpe_control_misses_the_reference_top(seed):
    ask = _tpe_ask(seed)
    assert max(ref.tpe_gaps(ask)) == 0.0
    ask["picks"] = ref.tpe_control_picks(ask)
    assert max(ref.tpe_gaps(ask)) > 1e-2
