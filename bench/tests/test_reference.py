"""The float64 references of the GP family's fit: the marginal
likelihood's gradient against finite differences, one fit against the
program's own on the CPU (float32 there at any precision), the schedule
of refits, and the fit gap."""
import math

import numpy as np
import pytest

from bench.lib import reference as ref


def _data(seed, n=60, d=5):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = np.sin(3.0 * X[:, 0]) + 0.3 * X[:, 1] ** 2 + rng.normal(0, 0.05, n)
    return X, y, (y - y.mean()) / (y.std() + 1e-6)


def _cold(d):
    return np.array([ref.COLD_LOG_LS] * d + [ref.COLD_LOG_VAR,
                                             ref.COLD_LOG_NOISE])


@pytest.mark.parametrize("seed", [0, 1])
def test_gradient_matches_finite_differences(seed):
    X, _, z = _data(seed)
    rng = np.random.default_rng(seed + 10)
    th = _cold(X.shape[1]) + 0.3 * rng.normal(size=X.shape[1] + 2)
    th[X.shape[1]] = 0.4                 # a variance above 1: jitter moves
    D2 = ref.sq_diffs(X)
    _, g = ref.nll_grad(th, D2, z)
    num = np.empty_like(th)
    for i in range(len(th)):
        e = np.zeros_like(th)
        e[i] = 1e-6
        num[i] = (ref.nll_grad(th + e, D2, z)[0]
                  - ref.nll_grad(th - e, D2, z)[0]) / 2e-6
    assert np.abs(num - g).max() < 1e-7 * max(1.0, np.abs(g).max())


def test_one_fit_matches_the_program():
    import jax.numpy as jnp

    from repro.core import gp
    X, y, _ = _data(2, n=80)
    d = X.shape[1]
    ym, ys = float(y.mean()), float(y.std()) + 1e-6
    mine = ref.adam_fit(_cold(d), X, (y - ym) / ys, 40)
    lls, lv, ln = gp.fit_hypers_bank(
        jnp.asarray(X[None], jnp.float32), jnp.asarray(y[None], jnp.float32),
        jnp.ones((1, len(y)), jnp.float32),
        jnp.full((1, d), math.log(0.5), jnp.float32),
        jnp.zeros((1,), jnp.float32),
        jnp.full((1,), math.log(1e-2), jnp.float32),
        jnp.asarray([ym], jnp.float32), jnp.asarray([ys], jnp.float32),
        steps=40)
    theirs = np.concatenate([np.asarray(lls)[0], np.asarray(lv),
                             np.asarray(ln)])
    assert np.abs(theirs - mine).max() < 1e-3
    ask = {"ref": {"theta": mine, "n_fit": len(y), "ym": ym, "ys": ys},
           "theta": theirs, "X": X, "y": y}
    assert 0.0 <= ref.fit_gap(ask) < 1e-5


def test_chain_refits_on_schedule():
    X, y, _ = _data(3, n=40)
    ch = ref.HyperChain(X, y, refit_every=8, steps=2)
    ch.at_ask(1)
    assert not ch.fitted                 # never under two observations
    ch.at_ask(20)
    assert ch.fitted and ch.n_fit == 20 and ch.fits == 1
    assert ch.ym == pytest.approx(y[:20].mean())
    ch.at_ask(27)
    assert ch.n_fit == 20                # seven since the last fit
    ch.at_ask(28)
    assert ch.n_fit == 28 and ch.fits == 2
    ch.y[29] = ch.ym + 2e3 * ch.ys      # far out: refits at once
    ch.at_ask(30)
    assert ch.n_fit == 30 and ch.fits == 3


def test_replay_follows_the_journal():
    X, y, _ = _data(4, n=30)
    # study 0 (GP) has 20 observations at the snapshot, 10 told after;
    # study 1 is TPE: its asks run no fit schedule
    ops = [{"seq": 101, "op": "ask", "study": 0}]
    ops += [{"seq": 102 + i, "op": "tell", "study": 0} for i in range(8)]
    ops += [{"seq": 110, "op": "ask", "study": 1},
            {"seq": 111, "op": "tell", "study": 0},
            {"seq": 112, "op": "ask", "study": 0},
            {"seq": 113, "op": "tell", "study": 0},
            {"seq": 114, "op": "ask", "study": 0}]
    out = ref.replay_fits(ops, {101: 0, 112: 0, 114: 0}, {0: (X, y)},
                          lambda b: b == 0, refit_every=8, steps=2)
    assert [out[s]["k"] for s in (101, 112, 114)] == [20, 29, 30]
    assert [out[s]["n_fit"] for s in (101, 112, 114)] == [20, 29, 29]
    assert out["fits"] == 2


def test_fit_gap_of_broken_hypers():
    X, y, z = _data(5)
    th = ref.adam_fit(_cold(X.shape[1]), X, z, 20)
    base = {"ref": {"theta": th, "n_fit": len(y), "ym": float(y.mean()),
                    "ys": float(y.std()) + 1e-6}, "X": X, "y": y}
    assert ref.fit_gap(dict(base, theta=th)) == 0.0
    assert ref.fit_gap(dict(base, theta=th * np.nan)) == math.inf
    worse = th.copy()
    worse[-1] += 2.0                     # noise seven times too large
    assert ref.fit_gap(dict(base, theta=worse)) > 1e-2
