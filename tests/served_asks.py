"""Asks served by the bank, recorded and judged the way the benchmark
judges the chip: ``bench.lib.serving.capture_asks`` records what an ask
saw (its observations, pending rows, candidates and picks) and
``bench.lib.reference`` scores the picks in float64.  Each GP-family
pick is judged under the bank's own fit (its hyperparameters and frozen
standardization), so a gap is the scoring's error, not the fit's.
"""
import numpy as np
from scipy import stats

from bench.lib import reference, serving
from repro.core import StudyBank

# largest pick gap (in units of 1 + |best|) a served pick may show.  On
# the noisy grids of test_served_{gp,cluster,tpe}.py and the bucket edges
# of test_studybank.py the CPU measured every gap at exactly 0, so the
# limit is float32's resolution; the noiseless near-tie grid measured up
# to 7.1e-4, and its limit is ten times that (the benchmark's own widest
# limit is 0.05)
GAP_LIMIT = {"gp": 1e-6, "cluster": 1e-6, "tpe": 1e-6}
NEAR_TIE_LIMIT = 7e-3

SPACE = {"x": stats.uniform(0, 1), "y": stats.uniform(0, 1)}
FIT_STEPS = 40


def quadratic(seed, n=20, noise=0.05):
    """``n`` observations of a quadratic bowl over SPACE (the encoding of a
    uniform(0, 1) parameter is its value), with Gaussian noise of sd
    ``noise``; ``noise=0`` drives the fitted GP noise down, K towards
    ill-conditioning, and leaves near-tied UCB scores."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(size=(n, 2))
    y = -np.sum((X - 0.6) ** 2, -1) + noise * rng.normal(size=n)
    return [({"x": float(a), "y": float(b)}, float(v))
            for (a, b), v in zip(X, y)]


def study(optimizer, obs, *, seed=0, mc_samples=300, space=SPACE,
          strategy_kwargs=None):
    """A bank of one study of ``optimizer`` holding the observations
    ``obs`` (``[(params, value), ...]``); returns ``(bank, view)``."""
    bank = StudyBank(space, 1, optimizer=optimizer, seed=seed,
                     mc_samples=mc_samples, fit_steps=FIT_STEPS,
                     strategy_kwargs=strategy_kwargs)
    view = bank.study(0)
    for params, value in obs:
        view.observe_params(params, value)
    return bank, view


def served(bank, view, n):
    """Ask ``view`` for ``n`` trials; returns ``(trials, ask record)``.  A
    bank without a journal keeps ``op_seq`` at 0, so the ask records as
    sequence 1."""
    with serving.capture_asks([bank.op_seq + 1]) as got:
        trials = view.ask(n)
    (ask,) = got
    if ask["family"] != "tpe":
        b = ask["study"]
        ask["ref"] = {"theta": ask["theta"],
                      "ym": float(bank.ledger.y_mean[b]),
                      "ys": float(bank.ledger.y_std[b])}
    return trials, ask


def gaps(ask):
    """The reference's gaps of the ask's picks (``reference.GAPS``)."""
    return reference.GAPS[ask["family"]](ask)
