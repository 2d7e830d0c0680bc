"""TPE asks on the served path (a study's view -> ``StudyBank`` ->
``tpe.fused_tpe_propose_bank``), judged by the benchmark's float64
reference (``tests/served_asks.py``), and the ``pending_penalty``
behaviour."""
import numpy as np
import pytest
from scipy import stats

from bench.lib.faults import changed
from served_asks import GAP_LIMIT, SPACE, gaps, quadratic, served, study

# one wide uniform dimension, a one-hot categorical whose observed columns
# are 0/1, and a dimension the observations crowd around 0.5: per-dim
# bandwidths differ by dimension here, where a d-global one would not
ANISO = {"x": stats.uniform(0, 1), "k": ["a", "b"],
         "z": stats.uniform(0, 1)}
# a 6 x 6 grid: 300 candidates repeat every configuration, so the naive
# top-b re-proposes the very configuration in flight
GRID = {"x": range(0, 6), "y": range(0, 6)}


def anisotropic(seed, n=24):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        p = {"x": float(rng.uniform()),
             "k": "b" if rng.uniform() < 0.3 else "a",
             "z": float(0.5 + 0.02 * rng.normal())}
        v = -(p["x"] - 0.6) ** 2 - 0.3 * (p["k"] == "b")
        out.append((p, v + 0.05 * float(rng.normal())))
    return out


def grid(seed, n=16):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x, y = int(rng.integers(6)), int(rng.integers(6))
        out.append(({"x": x, "y": y},
                    -((x - 4) ** 2 + (y - 1) ** 2) + 0.1 * rng.normal()))
    return out


SURFACES = {"isotropic": (quadratic, SPACE), "anisotropic": (anisotropic,
                                                              ANISO)}


@pytest.mark.parametrize("surface", sorted(SURFACES))
@pytest.mark.parametrize("mc_samples", [300, 600])
@pytest.mark.parametrize("seed", range(4))
def test_tpe_picks_are_the_reference_top_n(seed, mc_samples, surface):
    """The picks are the top ``n`` of the reference's l(x)/g(x) score, in
    order."""
    make, space = SURFACES[surface]
    bank, view = study("tpe", make(seed), seed=seed, mc_samples=mc_samples,
                       space=space)
    trials, ask = served(bank, view, 4)
    assert len(trials) == 4
    assert max(gaps(ask)) <= GAP_LIMIT["tpe"]


def test_tpe_naive_parallelism_ignores_pending():
    """Default (Hyperopt) semantics: trials in flight do not change the
    picks.  Two studies at the same RNG state, one with two trials in
    flight and one whose two trials failed, pick the same batch."""
    got = []
    for fail in (False, True):
        bank, view = study("tpe", quadratic(1), seed=1)
        for t in view.ask(2):
            if fail:
                view.tell_failed(t.id)
        got.append([t.params for t in view.ask(4)])
    assert got[0] == got[1]


def test_tpe_pending_penalty_breaks_topb_duplication():
    """An async replacement pick with the previous pick still in flight:
    naive TPE re-proposes the same configuration (top-b duplication); with
    ``pending_penalty`` the bad-split KDE rises around the pending point
    and the replacement moves elsewhere."""
    picks = {}
    for pen in (False, True):
        bank, view = study("tpe", grid(0), seed=0, space=GRID,
                           strategy_kwargs={"pending_penalty": pen})
        first = view.ask(1)[0].params
        picks[pen] = (first, view.ask(1)[0].params)
    assert picks[False][0] == picks[True][0]
    assert picks[False][1] == picks[False][0]
    assert picks[True][1] != picks[True][0]


def test_tpe_pending_penalty_batch_valid_unique_and_clamped():
    """With the penalty on and trials in flight, the batch is distinct
    valid candidates, clamped to the candidate count."""
    bank, view = study("tpe", quadratic(2), seed=2, mc_samples=3,
                       strategy_kwargs={"pending_penalty": True})
    view.ask(2)
    trials, ask = served(bank, view, 8)
    assert len(trials) == 3
    assert sorted(ask["picks"]) == [0, 1, 2]


@pytest.mark.parametrize("mc_samples", [3, 600])
def test_tpe_batch_valid_unique_and_clamped(mc_samples):
    bank, view = study("tpe", quadratic(5), seed=5, mc_samples=mc_samples)
    view.ask(3)
    trials, ask = served(bank, view, 4)
    assert len(trials) == min(4, mc_samples)
    assert min(ask["picks"]) >= 0
    assert len(set(ask["picks"])) == len(trials)
    assert all(0.0 <= t.params[k] <= 1.0 for t in trials for k in SPACE)


def test_tpe_shifted_picks_fail_the_gap_limit():
    """The control: picks shifted as the benchmark's ``answer`` fault
    shifts them lie far outside the gap limit."""
    bank, view = study("tpe", quadratic(5), seed=5)
    with changed("answer"):
        _, ask = served(bank, view, 4)
    assert max(gaps(ask)) > 1e-2
