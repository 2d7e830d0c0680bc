"""The strategy table: names, the checks on ``strategy_kwargs`` through
every entry point that takes them, the random draw and the k-means the
clustering head runs."""
import numpy as np
import pytest
from scipy import stats

from repro.core import AskTellOptimizer, StudyBank
from repro.core.kmeans import kmeans_assign
from repro.core.strategies import check_kwargs, random_picks
from repro.service.server import TuningService

SPACE = {"x": stats.uniform(0, 1), "y": stats.uniform(0, 1)}

# (optimizer, strategy_kwargs, the error it must raise)
BAD = [
    ("tpe", {"gamma": 0.6}, ValueError),
    ("tpe", {"gamme": 0.2}, TypeError),
    ("bayesian", {"scorer": "chol"}, TypeError),
    ("clustering", {"scorer": "kinv_jnp"}, TypeError),
    ("bayesian", {"gamma": 0.2}, TypeError),
    ("hallucination_ref", {}, ValueError),
]


def test_random_strategy_no_gp():
    picked = random_picks(100, 8, seed=0)
    assert len(set(picked)) == 8


def test_random_strategy_clamps_small_candidate_set():
    """batch_size > n_candidates (tiny mc_samples override) must degrade
    gracefully instead of raising ValueError from rng.choice."""
    picked = random_picks(3, 8, seed=0)
    assert sorted(int(p) for p in picked) == [0, 1, 2]


def test_strategy_kwargs_accepted_per_family():
    check_kwargs("tpe", {"gamma": 0.5, "pending_penalty": True}, 2, 1e4)
    check_kwargs("clustering", {"top_frac": 0.1}, 2, 1e4)
    check_kwargs("bayesian", {}, 2, 1e4)
    check_kwargs("random", {"anything": 1}, 2, 1e4)


@pytest.mark.parametrize("opt,kwargs,err", BAD)
def test_bad_strategy_config_refused_by_the_optimizer(opt, kwargs, err):
    with pytest.raises(err):
        AskTellOptimizer(SPACE, optimizer=opt,
                         strategy_kwargs=kwargs).ask(1)


@pytest.mark.parametrize("opt,kwargs,err", BAD)
def test_bad_strategy_config_refused_by_the_bank(opt, kwargs, err):
    """Refused before any study asks, in the random phase or the
    device phase alike."""
    if opt == "hallucination_ref":
        with pytest.raises(err, match="unknown optimizer"):
            StudyBank(SPACE, 2, optimizer=opt)
        return
    bank = StudyBank(SPACE, 2, optimizer=opt, strategy_kwargs=kwargs,
                     mc_samples=32)
    for p in bank.space.sample(3, np.random.default_rng(0)):
        bank.study(1).observe_params(p, p["x"])
    with pytest.raises(err):
        bank.ask_all(1)
    assert int(bank.ledger.n_trials[0]) == 0


@pytest.mark.parametrize("opt,kwargs,err", BAD)
def test_bad_strategy_config_refused_by_the_service_create(tmp_path, opt,
                                                           kwargs, err):
    """The create op is refused before it reaches the journal."""
    svc = TuningService(tmp_path, config={
        "space": {"x": {"uniform": [0.0, 1.0]}}, "max_studies": 2,
        "strategy_kwargs": kwargs})
    try:
        with pytest.raises(err):
            svc.create_study("s", optimizer=opt)
        assert svc.bank.op_seq == 0 and "s" not in svc._names
    finally:
        svc.close()


def _refusal(source, name, tmp_path):
    """The error that ``name`` meets when it comes from ``source``."""
    with pytest.raises(ValueError) as ei:
        if source == "config":
            TuningService(tmp_path / name, config={
                "space": {"x": {"uniform": [0.0, 1.0]}}, "max_studies": 1,
                "optimizer": name})
        elif source == "journal":
            StudyBank(SPACE, 1).apply_op(
                {"seq": 1, "op": "create", "study": 0, "optimizer": name})
        else:
            sd = StudyBank(SPACE, 1).state_dict()
            sd["strategies"] = [name]
            StudyBank(SPACE, 1).load_state_dict(sd)
    return str(ei.value).replace(repr(name), "NAME")


@pytest.mark.parametrize("source", ["config", "journal", "snapshot"])
def test_retired_optimizer_name_refused_like_any_unknown(tmp_path, source):
    """``hallucination_ref`` (the retired numpy reference strategy) is
    refused as any unknown name is, wherever it comes from."""
    got = _refusal(source, "hallucination_ref", tmp_path)
    assert "unknown optimizer" in got
    assert got == _refusal(source, "no_such_optimizer", tmp_path)


def test_kmeans_partitions():
    rng = np.random.default_rng(0)
    X = np.concatenate([rng.normal(0, 0.05, (30, 2)),
                        rng.normal(1, 0.05, (30, 2))]).astype(np.float32)
    w = np.ones(60, np.float32)
    a = kmeans_assign(X, w, 2, seed=0)
    assert set(a.tolist()) == {0, 1}
    # the two blobs end up in different clusters
    assert len(set(a[:30].tolist())) == 1
    assert a[0] != a[45]
