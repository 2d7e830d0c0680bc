"""Golden picks: fixed-seed banks must propose exactly what they proposed
before the single-study strategy engines were retired.

``tests/data/golden_picks.json`` holds the encoded picks of four small
banks (one per strategy family, and one mixed), recorded at the commit
that still carried those engines: five ``ask_all`` rounds with
closed-form tells, failures and trials left in flight, then one ask
through each study's own view.  The served programs did not change, so
every pick must match bit for bit.  This is a guard against behaviour
changes, not a reference: ``bench/lib/reference.py`` judges whether the
picks are good.

Re-record (only when a change is meant to move the picks) with
``PYTHONPATH=src python tests/test_golden_picks.py``.
"""
import json
from pathlib import Path

import numpy as np
import pytest
from scipy import stats

from repro.core import StudyBank

DATA = Path(__file__).resolve().parent / "data" / "golden_picks.json"
SPACE = {"x": stats.uniform(0, 1), "y": stats.uniform(-1, 2),
         "k": ["a", "b", "c"]}
SCENARIOS = {
    "gp": (["bayesian"] * 3, {}),
    "cluster": (["clustering"] * 3, {}),
    "tpe": (["tpe"] * 3, {"pending_penalty": True}),
    "mixed": (["bayesian", "clustering", "tpe"], {}),
}


def _value(p):
    off = {"a": 0.0, "b": -0.05, "c": 0.02}[p["k"]]
    return -(p["x"] - 0.3) ** 2 - 0.5 * (p["y"] - 0.4) ** 2 + off


def _rows(bank, trials):
    return np.asarray(bank.space.encode([t.params for t in trials]),
                      np.float32)


def picks(name):
    """Every ask's encoded picks, in order: ``[[study, rows], ...]``."""
    names, kwargs = SCENARIOS[name]
    bank = StudyBank(SPACE, len(names), optimizer=names, seed=17,
                     mc_samples=96, fit_steps=6, refit_every=4,
                     strategy_kwargs=kwargs)
    rng = np.random.default_rng(3)
    for b in range(len(names)):
        for p in bank.space.sample(10 + 3 * b, rng):
            bank.study(b).observe_params(p, _value(p))
    out = []
    for rnd in range(5):
        for b, ts in enumerate(bank.ask_all(2)):
            out.append([b, _rows(bank, ts).tolist()])
            bank.tell(b, ts[0].id, _value(ts[0].params))
            if rnd % 2:
                bank.tell_failed(b, ts[1].id)   # else left in flight
    for b in range(len(names)):
        out.append([b, _rows(bank, bank.study(b).ask(2)).tolist()])
    return out


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_picks_match_the_recorded_ones(name):
    want = json.loads(DATA.read_text())[name]
    got = picks(name)
    assert [b for b, _ in got] == [b for b, _ in want]
    for i, ((_, g), (_, w)) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32),
                                      err_msg=f"{name}: ask {i}")


if __name__ == "__main__":
    DATA.parent.mkdir(exist_ok=True)
    DATA.write_text(json.dumps({k: picks(k) for k in sorted(SCENARIOS)},
                               separators=(",", ":")) + "\n")
