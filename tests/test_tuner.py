import json

import numpy as np
import pytest
from scipy.stats import uniform

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # deterministic tests below still run
    HAVE_HYPOTHESIS = False

from repro.core import Tuner


def quad(p):
    return -(p["x"] - 0.7) ** 2 - (p["y"] - 0.2) ** 2


def serial_objective(batch):
    return [quad(p) for p in batch], list(batch)


SPACE = {"x": uniform(0, 1), "y": uniform(0, 1)}
FAST = dict(mc_samples=1500, fit_steps=15)


def test_maximize_beats_random_seeded():
    conf = dict(optimizer="bayesian", num_iteration=10, batch_size=3,
                seed=0, **FAST)
    res_b = Tuner(SPACE, serial_objective, conf).maximize()
    res_r = Tuner(SPACE, serial_objective,
                  {**conf, "optimizer": "random"}).maximize()
    assert res_b.best_objective >= res_r.best_objective - 1e-3
    assert res_b.best_objective > -0.01


def test_minimize():
    res = Tuner(SPACE, lambda b: ([-quad(p) for p in b], list(b)),
                dict(optimizer="clustering", num_iteration=8, batch_size=3,
                     seed=1, **FAST)).minimize()
    assert res.best_objective < 0.01  # minimizing the positive quadratic


def test_partial_results_and_reordering():
    """Paper §2.4: objective may return any subset in any order."""
    rng = np.random.default_rng(0)

    def flaky(batch):
        pairs = [(quad(p), p) for p in batch]
        rng.shuffle(pairs)
        keep = pairs[:max(1, len(pairs) - 2)]  # drop up to 2 per batch
        return [v for v, _ in keep], [p for _, p in keep]

    res = Tuner(SPACE, flaky, dict(optimizer="bayesian", num_iteration=8,
                                   batch_size=4, seed=2, **FAST)).maximize()
    assert res.n_failed > 0
    assert res.best_objective > -0.05
    assert len(res.objective_values) == len(res.params_tried)


def test_nan_and_exception_eval_dropped():
    def sometimes_nan(batch):
        out = []
        for i, p in enumerate(batch):
            out.append(float("nan") if i % 2 == 0 else quad(p))
        return out, list(batch)

    res = Tuner(SPACE, sometimes_nan,
                dict(optimizer="bayesian", num_iteration=5, batch_size=4,
                     seed=3, **FAST)).maximize()
    assert all(np.isfinite(v) for v in res.objective_values)
    assert res.n_failed >= 10


def test_empty_batches_survive():
    calls = {"n": 0}

    def dead_then_alive(batch):
        calls["n"] += 1
        if calls["n"] <= 2:
            return [], []  # total worker outage for 2 rounds
        return serial_objective(batch)

    res = Tuner(SPACE, dead_then_alive,
                dict(optimizer="bayesian", num_iteration=6, batch_size=2,
                     seed=4, **FAST)).maximize()
    assert res.best_objective > -0.2


def test_checkpoint_resume(tmp_path):
    ckpt = tmp_path / "tuner.json"
    conf = dict(optimizer="bayesian", num_iteration=6, batch_size=2, seed=5,
                checkpoint_path=str(ckpt), **FAST)
    full = Tuner(SPACE, serial_objective, conf).maximize()

    # restart from scratch with the same config: first tuner runs 3 iters
    ckpt2 = tmp_path / "tuner2.json"
    conf2 = {**conf, "checkpoint_path": str(ckpt2), "num_iteration": 3}
    Tuner(SPACE, serial_objective, conf2).maximize()
    state = json.loads(ckpt2.read_text())
    assert state["iteration"] == 3
    # resume to 6
    conf3 = {**conf2, "num_iteration": 6}
    resumed = Tuner(SPACE, serial_objective, conf3).maximize()
    assert resumed.iterations == 6
    assert len(resumed.objective_values) == len(full.objective_values)


def test_config_validation():
    with pytest.raises(ValueError):
        Tuner(SPACE, serial_objective, dict(optimizer="sgd"))
    with pytest.raises(ValueError):
        Tuner(SPACE, serial_objective, dict(nonsense=1))
    with pytest.raises(ValueError):
        bad = lambda b: ([1.0], [])  # mismatched lengths
        Tuner(SPACE, bad, dict(num_iteration=1)).maximize()


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.permutations(list(range(6))), st.integers(0, 1000))
    def test_observation_order_invariance(perm, seed):
        """The tuner's observed set is invariant to result ordering."""
        def permuting(batch):
            idx = [i for i in perm if i < len(batch)]
            return [quad(batch[i]) for i in idx], [batch[i] for i in idx]

        res = Tuner(SPACE, permuting,
                    dict(optimizer="random", num_iteration=3, batch_size=6,
                         seed=seed, mc_samples=500)).maximize()
        for v, p in zip(res.objective_values, res.params_tried):
            assert abs(v - quad(p)) < 1e-9
else:
    def test_observation_order_invariance():
        pytest.importorskip("hypothesis")


def test_checkpoint_resume_reproduces_remaining_proposals(tmp_path):
    """A Tuner resumed from checkpoint_path proposes the same remaining
    configurations as an uninterrupted run (the bank's fit schedule is
    restored from the checkpointed ``gp`` entry)."""
    space = {"x": uniform(0, 1), "y": uniform(0, 1)}
    conf = dict(optimizer="bayesian", num_iteration=6, batch_size=2, seed=7,
                refit_every=4, mc_samples=1200, fit_steps=15)
    full = Tuner(space, serial_objective, conf).maximize()

    ckpt = tmp_path / "t.json"
    conf_i = {**conf, "checkpoint_path": str(ckpt), "num_iteration": 3}
    Tuner(space, serial_objective, conf_i).maximize()
    assert json.loads(ckpt.read_text())["iteration"] == 3
    resumed = Tuner(space, serial_objective,
                    {**conf_i, "num_iteration": 6}).maximize()
    assert resumed.iterations == 6
    full_xy = [(p["x"], p["y"]) for p in full.params_tried]
    res_xy = [(p["x"], p["y"]) for p in resumed.params_tried]
    assert res_xy == full_xy
    assert resumed.objective_values == full.objective_values
