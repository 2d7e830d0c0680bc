"""GP-BUCB asks on the served path (a study's view -> ``StudyBank`` ->
``gp.bank_absorb`` / ``gp.bank_pick``), judged by the benchmark's float64
reference (``tests/served_asks.py``)."""
import numpy as np
import pytest

from bench.lib import reference
from bench.lib.faults import changed
from repro.core import AskTellOptimizer, StudyBank
from served_asks import (GAP_LIMIT, NEAR_TIE_LIMIT, SPACE, gaps, quadratic,
                         served, study)


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("pending", [0, 3])
@pytest.mark.parametrize("seed", range(8))
def test_gp_bucb_picks_match_the_reference(seed, pending, n):
    """Each slot's pick is the reference's best UCB among the candidates
    not yet picked, the variance conditioned on the observations, the
    trials in flight and the earlier picks."""
    bank, view = study("bayesian", quadratic(seed), seed=seed)
    if pending:
        view.ask(pending)        # left in flight: absorbed by bank_absorb
    trials, ask = served(bank, view, n)
    assert len(trials) == n and len(ask["P"]) == pending
    assert max(gaps(ask)) <= GAP_LIMIT["gp"]


@pytest.mark.parametrize("pending", [0, 3])
@pytest.mark.parametrize("seed", range(8))
def test_gp_bucb_near_ties_on_noiseless_surfaces(seed, pending):
    """Noiseless surfaces: the fit drives the noise more than ten times
    below its cold start of 1e-2, K grows ill-conditioned, and near-tied
    UCB scores probe the float32 scoring's conditioning."""
    bank, view = study("bayesian", quadratic(seed, noise=0.0), seed=seed)
    if pending:
        view.ask(pending)
    _, ask = served(bank, view, 4)
    assert reference.hypers(ask["theta"])[2] < 1e-3
    assert max(gaps(ask)) <= NEAR_TIE_LIMIT


@pytest.mark.parametrize("mc_samples", [3, 600])
def test_gp_bucb_batch_valid_unique_and_clamped(mc_samples):
    """A batch of distinct valid candidates, clamped to the candidate
    count when a small ``mc_samples`` leaves fewer than asked for."""
    bank, view = study("bayesian", quadratic(5), seed=5,
                       mc_samples=mc_samples)
    view.ask(3)
    trials, ask = served(bank, view, 4)
    assert len(trials) == min(4, mc_samples)
    assert min(ask["picks"]) >= 0
    assert len(set(ask["picks"])) == len(trials)
    assert all(0.0 <= t.params[k] <= 1.0 for t in trials for k in SPACE)


def test_gp_bucb_shifted_picks_fail_the_gap_limit():
    """The control: picks shifted as the benchmark's ``answer`` fault
    shifts them lie far outside the gap limit."""
    bank, view = study("bayesian", quadratic(5), seed=5)
    view.ask(3)
    with changed("answer"):
        _, ask = served(bank, view, 4)
    assert max(gaps(ask)) > NEAR_TIE_LIMIT


def test_default_strategy_is_fused():
    """The default optimizer is served by the bank's GP family, on the
    fused bank pipeline."""
    assert AskTellOptimizer(SPACE).optimizer == "bayesian"
    bank = StudyBank(SPACE, 2)
    assert bank.strategy_names == ["bayesian", "bayesian"]
    assert bank._fams == {0: "gp", 1: "gp"}


@pytest.mark.parametrize("noise", [1e-1, 1e-3, 1e-5])
def test_cond_estimate_within_2x_of_true(noise):
    """The power-iteration estimate (``scoring.cond_estimate``, which
    ``bank_factors`` runs for the ``factors.ill_conditioned`` counter)
    lands within 2x of ``np.linalg.cond`` on masked identity-padded RBF
    kernels — the diagonal bound sat 20-50x low."""
    import jax.numpy as jnp

    from repro.core import scoring

    rng = np.random.default_rng(0)
    for na, n in [(32, 20), (64, 49)]:
        X = rng.uniform(size=(n, 3)).astype(np.float32)
        d2 = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
        K = (np.exp(-0.5 * d2)
             + np.eye(n) * noise).astype(np.float32)
        true = np.linalg.cond(K.astype(np.float64))
        Kp = np.eye(na, dtype=np.float32)
        Kp[:n, :n] = K
        L = np.linalg.cholesky(Kp.astype(np.float64)).astype(np.float32)
        mask = np.zeros(na, np.float32)
        mask[:n] = 1.0
        est = float(scoring.cond_estimate(jnp.asarray(L),
                                          jnp.asarray(mask)))
        assert true / 2.0 <= est <= true * 2.0, (na, n, est, true)
