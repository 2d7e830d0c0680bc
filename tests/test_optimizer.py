"""The ask/tell core: ledger invariants, pending-trial hallucination
(single device program per pick), sync<->async parity, and kill/resume
determinism in both execution modes."""
import json
import threading

import numpy as np
import pytest
from scipy.stats import uniform

import repro.core.gp as gp_mod
from repro.core import AskTellOptimizer, AsyncTuner, Tuner, TunerResults
from repro.core.strategies import check_kwargs
from repro.scheduler.base import TaskHandle

SPACE = {"x": uniform(0, 1), "y": uniform(0, 1)}
FAST = dict(mc_samples=500, fit_steps=10)


def quad(p):
    return -(p["x"] - 0.7) ** 2 - (p["y"] - 0.2) ** 2


class InlineScheduler:
    """Deterministic async scheduler: trials complete synchronously inside
    ``submit``, and ``wait_any`` hands back one completion at a time in
    dispatch order — the async loop becomes a reproducible sequence."""

    def submit(self, fn, params):
        h = TaskHandle(params)
        try:
            h.result = float(fn(params))
        except Exception as e:  # noqa: BLE001
            h.error = e
        h.done.set()
        return h

    def wait_any(self, handles, timeout=None):
        done = [h for h in handles if h.done.is_set()]
        return done[:1]


# --------------------------------------------------------------------- ledger
def test_ask_ids_unique_and_monotonic():
    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    ids = [t.id for t in opt.ask(3)] + [t.id for t in opt.ask(2)]
    assert len(set(ids)) == 5
    assert ids == sorted(ids)


def test_tell_before_ask_rejected():
    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    with pytest.raises(KeyError):
        opt.tell(0, 1.0)
    with pytest.raises(KeyError):
        opt.tell_failed(17)


def test_double_tell_rejected():
    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    (t,) = opt.ask(1)
    opt.tell(t.id, 0.5)
    with pytest.raises(ValueError):
        opt.tell(t.id, 0.5)
    with pytest.raises(ValueError):
        opt.tell_failed(t.id)


def test_observe_params_invalid_leaves_state_untouched():
    """A failing observe must be a no-op: no phantom trial, no burned id.
    The durable service journals observes before applying them, so live
    state diverging from what replay reconstructs would break bit-exact
    recovery."""
    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    with pytest.raises(KeyError):
        opt.observe_params({"bogus": 1.0}, 0.5)      # not in the space
    with pytest.raises(TypeError):
        opt.observe_params({"x": 0.5, "y": 0.5}, None)
    assert opt.num_trials == 0 and opt.n_observed == 0
    t = opt.observe_params({"x": 0.5, "y": 0.5}, 0.5)
    assert t.id == 0 and t.status == "observed"


def test_failed_and_nonfinite_trials_never_observed():
    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    a, b, c = opt.ask(3)
    opt.tell(a.id, 1.0)
    opt.tell_failed(b.id)
    opt.tell(c.id, float("nan"))   # non-finite counts as a failure
    assert opt.n_observed == 1
    assert opt.n_failed == 2
    res = opt.results()
    assert res.objective_values == [1.0]
    assert res.n_failed == 2
    # the GP only ever sees the observed row
    assert [t.id for t in opt.observed_trials()] == [a.id]


def test_minimize_sign_handling():
    opt = AskTellOptimizer(SPACE, seed=0, sign=-1.0, **FAST)
    a, b = opt.ask(2)
    opt.tell(a.id, 3.0)
    opt.tell(b.id, 1.0)
    res = opt.results()
    assert res.best_objective == 1.0   # smaller raw value wins


# ---------------------------------------------------- pending hallucination
def test_async_pick_is_single_gp_program(monkeypatch):
    """A replacement pick with k pending trials must dispatch the staged
    bank pipeline exactly once — one ``bank_absorb`` + one ``bank_pick``
    per ask, never one posterior+append program per pending trial (the
    seed's host loop).  Single-study asks route through the bank-of-one
    engine."""
    calls = {"bank_pick": 0, "bank_absorb": 0}
    orig_pick = gp_mod.bank_pick
    orig_absorb = gp_mod.bank_absorb

    def count(key, orig):
        def wrapper(*a, **k):
            calls[key] += 1
            return orig(*a, **k)
        return wrapper

    monkeypatch.setattr(gp_mod, "bank_pick", count("bank_pick", orig_pick))
    monkeypatch.setattr(gp_mod, "bank_absorb",
                        count("bank_absorb", orig_absorb))

    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    for t in opt.ask(4):               # random phase (no GP yet)
        opt.tell(t.id, quad(t.params))
    opt.ask(3)                         # no pending -> pick only, no absorb
    assert calls["bank_pick"] == 1 and calls["bank_absorb"] == 0
    opt.ask(2)                         # 3 pending -> ONE absorb + ONE pick
    assert calls["bank_pick"] == 2 and calls["bank_absorb"] == 1


# ----------------------------------------------------- sync <-> async parity
def test_sync_async_pick_parity_on_fixed_seed():
    """With a strictly sequential schedule (batch_size=1, deterministic
    inline completion) the async event loop proposes exactly the sync batch
    loop's configurations: one shared core, no duplicated propose logic."""
    conf = dict(optimizer="bayesian", num_iteration=6, batch_size=1,
                initial_random=2, seed=11, **FAST)
    sync = Tuner(SPACE, lambda b: ([quad(p) for p in b], list(b)),
                 conf).maximize()
    anc = AsyncTuner(SPACE, quad, InlineScheduler(), num_evals=8,
                     batch_size=1, initial_random=2, seed=11,
                     **FAST).maximize()
    assert isinstance(anc, TunerResults)
    sync_xy = [(p["x"], p["y"]) for p in sync.params_tried]
    async_xy = [(p["x"], p["y"]) for p in anc.params_tried]
    assert async_xy == sync_xy
    assert anc.objective_values == sync.objective_values


# -------------------------------------------------------- kill/resume replay
def test_state_dict_roundtrip_mid_flight_pending():
    """Killing with trials in flight: the JSON state_dict carries the
    pending ledger, and the restored core replays the remaining proposals
    exactly (same RNG stream, same GP fit/append schedule)."""
    opt1 = AskTellOptimizer(SPACE, seed=3, **FAST)
    for t in opt1.ask(3):
        opt1.tell(t.id, quad(t.params))
    batch = opt1.ask(2)                       # leave 2 pending
    sd = json.loads(json.dumps(opt1.state_dict()))

    opt2 = AskTellOptimizer(SPACE, seed=999, **FAST)  # seed overwritten
    opt2.load_state_dict(sd)
    restored = opt2.pending_trials()
    assert [t.id for t in restored] == [t.id for t in batch]
    assert [(t.params["x"], t.params["y"]) for t in restored] == \
        [(t.params["x"], t.params["y"]) for t in batch]

    for opt, pend in ((opt1, batch), (opt2, restored)):
        for t in pend:
            opt.tell(t.id, quad(t.params))
    nxt1 = [(t.params["x"], t.params["y"]) for t in opt1.ask(2)]
    nxt2 = [(t.params["x"], t.params["y"]) for t in opt2.ask(2)]
    assert nxt1 == nxt2


def test_async_kill_resume_reproduces_remaining_proposals(tmp_path):
    """An async run stopped mid-flight resumes from its checkpoint to the
    exact proposals of an uninterrupted run — in-flight trials are
    re-dispatched from the serialized ledger."""
    kw = dict(num_evals=10, batch_size=2, initial_random=2, seed=7, **FAST)
    full = AsyncTuner(SPACE, quad, InlineScheduler(), **kw).maximize()

    ckpt = tmp_path / "async.json"
    # "kill" after 5 completions: early_stopping exits the loop leaving
    # in-flight trials pending in the checkpointed ledger
    stopped = AsyncTuner(SPACE, quad, InlineScheduler(),
                         checkpoint_path=str(ckpt),
                         early_stopping=lambda r: r.iterations >= 5,
                         **kw).maximize()
    assert stopped.iterations == 5
    state = json.loads(ckpt.read_text())
    assert any(t["status"] == "pending"
               for t in state["optimizer"]["trials"])

    resumed = AsyncTuner(SPACE, quad, InlineScheduler(),
                         checkpoint_path=str(ckpt), **kw).maximize()
    full_xy = [(p["x"], p["y"]) for p in full.params_tried]
    res_xy = [(p["x"], p["y"]) for p in resumed.params_tried]
    assert res_xy == full_xy
    assert resumed.objective_values == full.objective_values


def test_sync_kill_resume_via_state_dict(tmp_path):
    """Same guarantee through the sync driver's checkpoint file (which is
    now just iteration + the core's state_dict)."""
    conf = dict(optimizer="bayesian", num_iteration=6, batch_size=2,
                seed=5, refit_every=4, **FAST)
    objective = lambda b: ([quad(p) for p in b], list(b))  # noqa: E731
    full = Tuner(SPACE, objective, conf).maximize()

    ckpt = tmp_path / "sync.json"
    conf_i = {**conf, "checkpoint_path": str(ckpt), "num_iteration": 3}
    Tuner(SPACE, objective, conf_i).maximize()
    assert json.loads(ckpt.read_text())["iteration"] == 3
    resumed = Tuner(SPACE, objective,
                    {**conf_i, "num_iteration": 6}).maximize()
    assert [(p["x"], p["y"]) for p in resumed.params_tried] == \
        [(p["x"], p["y"]) for p in full.params_tried]


# ------------------- checkpoint round-trips through the hardened core
@pytest.mark.parametrize("optimizer", ["bayesian", "clustering"])
def test_sync_kill_resume_with_hardened_scorer(tmp_path, optimizer):
    """Kill/resume replay reproduces identical picks for both pick heads
    of the shared factor-scoring core: the checkpoint's GP fit schedule
    must replay the hardened (L, L^{-1}) factors bit-for-bit in the sync
    driver."""
    conf = dict(optimizer=optimizer, num_iteration=5, batch_size=2,
                seed=11, refit_every=4, **FAST)
    objective = lambda b: ([quad(p) for p in b], list(b))  # noqa: E731
    full = Tuner(SPACE, objective, conf).maximize()

    ckpt = tmp_path / "hardened.json"
    conf_i = {**conf, "checkpoint_path": str(ckpt), "num_iteration": 2}
    Tuner(SPACE, objective, conf_i).maximize()
    resumed = Tuner(SPACE, objective,
                    {**conf_i, "num_iteration": 5}).maximize()
    assert [(p["x"], p["y"]) for p in resumed.params_tried] == \
        [(p["x"], p["y"]) for p in full.params_tried]
    assert resumed.objective_values == full.objective_values


def test_async_kill_resume_with_hardened_scorer(tmp_path):
    """Async kill/resume through the factor core: in-flight trials
    re-dispatch from the ledger and the replacement picks (which absorb
    pending rows via the hardened ``scoring.absorb_pending`` loop inside
    the device program) replay identically."""
    kw = dict(num_evals=8, batch_size=2, initial_random=2, seed=21,
              **FAST)
    full = AsyncTuner(SPACE, quad, InlineScheduler(), **kw).maximize()

    ckpt = tmp_path / "hardened_async.json"
    stopped = AsyncTuner(SPACE, quad, InlineScheduler(),
                         checkpoint_path=str(ckpt),
                         early_stopping=lambda r: r.iterations >= 4,
                         **kw).maximize()
    assert stopped.iterations == 4
    resumed = AsyncTuner(SPACE, quad, InlineScheduler(),
                         checkpoint_path=str(ckpt), **kw).maximize()
    assert [(p["x"], p["y"]) for p in resumed.params_tried] == \
        [(p["x"], p["y"]) for p in full.params_tried]
    assert resumed.objective_values == full.objective_values


def test_state_dict_format_unchanged_by_scoring_core():
    """The unified core must not change the serialized format: version
    stays 1, the key set is stable, and the GP snapshot still carries only
    the fit schedule (n_fit + raw log-params) — the tracked factor is a
    pure function of those, so no migration shim is needed."""
    opt = AskTellOptimizer(SPACE, seed=0, **FAST)
    for t in opt.ask(3):
        opt.tell(t.id, quad(t.params))
    opt.ask(1)
    sd = json.loads(json.dumps(opt.state_dict()))
    assert sd["version"] == 1
    assert set(sd) == {"version", "next_id", "ask_count", "n_failed",
                       "sign", "best_trace", "trials", "rng_state", "gp"}
    assert set(sd["gp"]) == {"n_fit", "log_params"}
    assert set(sd["gp"]["log_params"]) == {"log_ls", "log_var", "log_noise"}


# ------------------------------------------------------------ driver surface
def test_async_tuner_returns_tuner_results_with_trace():
    res = AsyncTuner(SPACE, quad, InlineScheduler(), num_evals=6,
                     batch_size=2, initial_random=2, seed=1,
                     **FAST).maximize()
    assert isinstance(res, TunerResults)
    assert len(res.objective_values) == 6
    assert len(res.best_trace) == 6          # one snapshot per completion
    assert res.best_trace == sorted(res.best_trace)  # maximizing
    # legacy dict-style access still works
    assert res["best_objective"] == res.best_objective


def test_tuner_accepts_scheduler_config_key():
    from repro.scheduler import SerialScheduler
    res = Tuner(SPACE, quad,
                dict(scheduler=SerialScheduler(), optimizer="bayesian",
                     num_iteration=4, batch_size=2, seed=2,
                     **FAST)).maximize()
    assert res.best_objective > -0.2
    assert len(res.objective_values) == 2 + 4 * 2


def test_out_of_order_tells_keep_incremental_gp_path(monkeypatch):
    """Async completions land out of ask order; the fits still follow the
    refit_every schedule (a fit when an ask sees ``refit_every`` or more
    observations since the last one), not one per tell."""
    fits = []
    orig_fit = gp_mod.fit_hypers_bank

    def counting_fit(*a, **k):
        fits.append(int(np.asarray(a[2]).sum()))   # observations fit on
        return orig_fit(*a, **k)

    monkeypatch.setattr(gp_mod, "fit_hypers_bank", counting_fit)
    rng = np.random.default_rng(0)
    opt = AskTellOptimizer(SPACE, seed=0, mc_samples=400, fit_steps=10,
                           refit_every=8)
    inflight = list(opt.ask(4))
    n_done = 0
    seen = []                        # observations each model ask saw
    while n_done < 40:
        t = inflight.pop(rng.integers(len(inflight)))  # random completion
        opt.tell(t.id, quad(t.params))
        n_done += 1
        if n_done + len(inflight) < 40:
            if n_done >= 2:
                seen.append(n_done)
            inflight.extend(opt.ask(1))
    # the schedule, replayed over the counts the asks saw: the first
    # model ask fits, then every ask 8 or more observations on
    want = []
    for k in seen:
        if not want or k - want[-1] >= 8:
            want.append(k)
    assert fits == want
    assert len(fits) == 5             # one fit per tell would be 33


def test_objective_may_return_transformed_params():
    """Legacy contract: the objective may return *transformed* configs;
    they count as observations (not failures) and the returned params are
    authoritative in the results."""
    def transforming(batch):
        return ([quad(p) for p in batch],
                [dict(p, fold=1) for p in batch])

    res = Tuner(SPACE, transforming,
                dict(optimizer="bayesian", num_iteration=4, batch_size=2,
                     initial_random=2, seed=0, **FAST)).maximize()
    assert res.n_failed == 0
    assert len(res.objective_values) == 2 + 4 * 2
    assert all(p.get("fold") == 1 for p in res.params_tried)


def test_condition_wait_wakes_on_completion():
    """wait_any blocks on the scheduler's condition variable and returns as
    soon as a trial lands — not after a poll interval."""
    from repro.scheduler import TaskQueueScheduler
    sched = TaskQueueScheduler(n_workers=1)
    release = threading.Event()

    def gated(p):
        release.wait(5.0)
        return 1.0

    h = sched.submit(gated, {"x": 0.5})
    assert sched.wait_any([h], timeout=0.05) == []   # still blocked
    release.set()
    done = sched.wait_any([h], timeout=5.0)
    assert done == [h] and h.result == 1.0
    sched.shutdown()


# --------------------------------------------------- TPE through the core
def test_tpe_sync_kill_resume_replays_proposals(tmp_path):
    """TPE (no GP: ledger + RNG only) through the sync driver's checkpoint:
    a run stopped at iteration 3 resumes to the exact proposals of an
    uninterrupted one."""
    conf = dict(optimizer="tpe", num_iteration=6, batch_size=2, seed=5,
                **FAST)
    objective = lambda b: ([quad(p) for p in b], list(b))  # noqa: E731
    full = Tuner(SPACE, objective, conf).maximize()

    ckpt = tmp_path / "tpe_sync.json"
    conf_i = {**conf, "checkpoint_path": str(ckpt), "num_iteration": 3}
    Tuner(SPACE, objective, conf_i).maximize()
    resumed = Tuner(SPACE, objective,
                    {**conf_i, "num_iteration": 6}).maximize()
    assert [(p["x"], p["y"]) for p in resumed.params_tried] == \
        [(p["x"], p["y"]) for p in full.params_tried]


def test_tpe_async_kill_resume_replays_proposals(tmp_path):
    """Same guarantee through the async driver, with in-flight TPE trials
    serialized in the ledger and re-dispatched on resume."""
    kw = dict(optimizer="tpe", num_evals=10, batch_size=2,
              initial_random=2, seed=7, **FAST)
    full = AsyncTuner(SPACE, quad, InlineScheduler(), **kw).maximize()

    ckpt = tmp_path / "tpe_async.json"
    stopped = AsyncTuner(SPACE, quad, InlineScheduler(),
                         checkpoint_path=str(ckpt),
                         early_stopping=lambda r: r.iterations >= 5,
                         **kw).maximize()
    assert stopped.iterations == 5
    resumed = AsyncTuner(SPACE, quad, InlineScheduler(),
                         checkpoint_path=str(ckpt), **kw).maximize()
    assert [(p["x"], p["y"]) for p in resumed.params_tried] == \
        [(p["x"], p["y"]) for p in full.params_tried]
    assert resumed.objective_values == full.objective_values


def test_tpe_ask_is_single_device_program(monkeypatch):
    """Every TPE ask — pending trials included — must dispatch exactly one
    bank-serving fused device program (``fused_tpe_propose_bank``, which
    vmaps the per-row kernel over the study axis)."""
    import repro.core.tpe as tpe_mod

    calls = {"fused": 0}
    orig = tpe_mod.fused_tpe_propose_bank

    def counting(*a, **k):
        calls["fused"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(tpe_mod, "fused_tpe_propose_bank", counting)

    opt = AskTellOptimizer(
        SPACE, optimizer="tpe", seed=0,
        strategy_kwargs={"pending_penalty": True}, **FAST)
    for t in opt.ask(4):               # random phase (no model yet)
        opt.tell(t.id, quad(t.params))
    assert calls["fused"] == 0
    opt.ask(3)                         # no pending
    assert calls["fused"] == 1
    opt.ask(2)                         # 3 pending, absorbed in-program
    assert calls["fused"] == 2


def test_strategy_kwargs_forwarded_and_validated(monkeypatch):
    """The core forwards strategy_kwargs to the bank's TPE program;
    unknown keys surface as TypeError at first ask (the old TPEStrategy
    silently swallowed them)."""
    import repro.core.tpe as tpe_mod

    gammas = []
    orig = tpe_mod.fused_tpe_propose_bank

    def spy(X, y, C, meta, **k):
        gammas.append(float(np.asarray(meta)[0, 3]))
        return orig(X, y, C, meta, **k)

    monkeypatch.setattr(tpe_mod, "fused_tpe_propose_bank", spy)
    opt = AskTellOptimizer(SPACE, optimizer="tpe", seed=0,
                           strategy_kwargs={"gamma": 0.5}, **FAST)
    for t in opt.ask(2):
        opt.tell(t.id, quad(t.params))
    opt.ask(1)
    assert gammas == [0.5]

    bad = AskTellOptimizer(SPACE, optimizer="tpe", seed=0,
                           strategy_kwargs={"gamme": 0.5}, **FAST)
    with pytest.raises(TypeError):   # checked on the first ask
        bad.ask(1)


def test_tpe_gamma_validation():
    with pytest.raises(ValueError):
        check_kwargs("tpe", {"gamma": 0.0}, 2, 1e4)
    with pytest.raises(ValueError):
        check_kwargs("tpe", {"gamma": 0.6}, 2, 1e4)   # good quantile
    with pytest.raises(ValueError):                    # capped at 0.5:
        check_kwargs("tpe", {"gamma": 1.0}, 2, 1e4)   # disjoint splits
    with pytest.raises(ValueError):
        check_kwargs("tpe", {}, 0, 1e4)
    check_kwargs("tpe", {"gamma": 0.5}, 2, 1e4)       # boundary is valid
