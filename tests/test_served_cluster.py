"""Clustering asks on the served path (a study's view -> ``StudyBank`` ->
``gp.bank_cluster_pick``), judged by the benchmark's float64 reference
(``tests/served_asks.py``)."""
import pytest

from bench.lib.faults import changed
from served_asks import GAP_LIMIT, SPACE, gaps, quadratic, served, study


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("pending", [0, 3])
@pytest.mark.parametrize("seed", range(4))
def test_cluster_picks_match_the_reference(seed, pending, n):
    """The batch holds the reference's best UCB, every pick lies in the
    UCB's top ``n_top`` set, and no candidate is picked twice (the
    reference counts a repeat as the whole UCB range).  At batch 1 the
    pick is the UCB argmax."""
    bank, view = study("clustering", quadratic(seed), seed=seed)
    if pending:
        view.ask(pending)
    trials, ask = served(bank, view, n)
    assert len(trials) == n and len(ask["P"]) == pending
    assert max(gaps(ask)) <= GAP_LIMIT["cluster"]


@pytest.mark.parametrize("mc_samples", [3, 600])
def test_cluster_batch_valid_unique_and_clamped(mc_samples):
    """Distinct valid picks, clamped to the candidate count.  At three
    candidates k-means leaves clusters empty, and the backfill must not
    pick a candidate twice."""
    bank, view = study("clustering", quadratic(6), seed=6,
                       mc_samples=mc_samples)
    view.ask(3)
    trials, ask = served(bank, view, 4)
    assert len(trials) == min(4, mc_samples)
    assert min(ask["picks"]) >= 0
    assert len(set(ask["picks"])) == len(trials)
    assert all(0.0 <= t.params[k] <= 1.0 for t in trials for k in SPACE)


def test_cluster_shifted_picks_fail_the_gap_limit():
    """The control: picks shifted as the benchmark's ``answer`` fault
    shifts them lie far outside the gap limit."""
    bank, view = study("clustering", quadratic(5), seed=5)
    view.ask(3)
    with changed("answer"):
        _, ask = served(bank, view, 4)
    assert max(gaps(ask)) > 1e-2
