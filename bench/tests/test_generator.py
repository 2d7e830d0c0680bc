"""The traffic generator's schedule and its due-time accounting."""
import json
from pathlib import Path

import pytest

from bench.generators import worker_pools as wp
from bench.lib import deployment as dep

BENCH = Path(__file__).resolve().parents[1]


def _mix(name="xgb32_zipf"):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _cfg(name="xgb_fleet32"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _job(seed, mix=None, cfg=None, seconds=30.0):
    mix, cfg = mix or _mix(), cfg or _cfg()
    plan = dep.plan_lengths(cfg, mix, seed)
    return {"mix": mix, "cfg": cfg, "names": dep.study_names(cfg),
            "pools": plan["pools"], "seed": seed, "t_start": 100.0,
            "t0": 100.0 + dep.lead_in_s(mix),
            "t_end": 100.0 + seconds + dep.lead_in_s(mix), "grace_s": 60.0,
            "durations": dep.eval_durations(mix, seconds)}


@pytest.mark.parametrize("seed", [1, 2**31 + 12345])
def test_same_seed_same_inputs(seed):
    assert dep.plan_lengths(_cfg(), _mix(), seed) == dep.plan_lengths(
        _cfg(), _mix(), seed)
    assert dep.seeded_history(_cfg(), [5, 7], seed) == dep.seeded_history(
        _cfg(), [5, 7], seed)
    other = dep.seeded_history(_cfg(), [5, 7], seed + 1)
    assert other != dep.seeded_history(_cfg(), [5, 7], seed)


def test_every_seed_offers_the_same_schedule():
    a, b = _job(3), _job(2**31 + 4)
    assert wp.worker_plan(a) == wp.worker_plan(b)
    la = dep.plan_lengths(_cfg(), _mix(), 3)["lengths"]
    lb = dep.plan_lengths(_cfg(), _mix(), 2**31 + 4)["lengths"]
    assert sorted(la) == sorted(lb) and la != lb


def test_offered_rate_is_the_mix_rate():
    job = _job(5)
    plans = wp.worker_plan(job)
    ed = dep.mean_eval_s(job["mix"])
    firsts = sorted(p["first_due"] - job["t_start"] for p in plans)
    assert 0.0 < firsts[0] and firsts[-1] < ed
    flat = [d for p in plans for d in p["durations"]]
    assert sum(flat) / len(flat) == pytest.approx(ed, rel=0.05)


@pytest.mark.parametrize("rate", [1.2, 2.4, 4.5])
def test_plan_keeps_one_bucket(rate):
    c, m = _cfg(), dict(_mix(), rate=rate)
    plan = dep.plan_lengths(c, m, 7)
    n, na = c["ask_n"], plan["na"]
    assert max(plan["lengths"]) + 4 + n > na // 2
    ends = [a + g for a, g in zip(plan["lengths"], plan["gain"])]
    assert max(ends) + plan["pend_cap_max"] + n <= na
    assert sum(plan["pools"]) == m["workers"]


def test_zipf_pools():
    p = dep.pool_sizes({"workers": 64, "pools": {"kind": "zipf", "s": 0.99}},
                       32)
    assert sum(p) == 64 and p == sorted(p, reverse=True) and p[0] == 15


class FakeClock:
    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class StallingClient:
    """Answers at once, except that one ask takes ``stall`` seconds."""

    def __init__(self, clock, stall_on: int, stall: float):
        self.clock, self.stall_on, self.stall = clock, stall_on, stall
        self.asks = 0
        self.next_id = 0

    def ask(self, name, n, req_id):
        self.asks += 1
        if self.asks == self.stall_on:
            self.clock.t += self.stall
        out = [{"id": self.next_id + i, "params": {
            "learning_rate": 0.1, "gamma": 1.0, "max_depth": 5,
            "n_estimators": 100, "booster": "gbtree"}} for i in range(n)]
        self.next_id += n
        return {"trials": out}

    def tell(self, name, tid, value):
        return {}

    def tell_failed(self, name, tid):
        return {}


def test_due_times_ignore_a_stall():
    job = _job(11, seconds=120.0)
    plan = wp.worker_plan(job)[0]
    clock = FakeClock(job["t_start"])
    client = StallingClient(clock, stall_on=2, stall=5.0)
    recs = []
    wp.worker_loop(job, 0, plan, client, recs, clock=clock,
                   sleep=clock.sleep)
    asks = [r for r in recs if r["kind"] == "ask"]
    assert len(asks) >= 3
    # each ask is due one evaluation after the one before, whatever the
    # replies did; the stalled ask's reply is 5 s late, and the requests
    # after it are timed from their own due times
    durs = plan["durations"]
    for k in range(1, len(asks)):
        assert asks[k]["due"] == pytest.approx(asks[k - 1]["due"]
                                               + durs[k - 1])
    stalled = asks[1]
    assert stalled["done"] - stalled["due"] == pytest.approx(5.0)
    after = [r for r in recs if r["due"] > stalled["due"]]
    assert after and after[0]["sent"] >= stalled["done"]
    # the generator itself was never late: a request waits only for its
    # worker's previous reply
    assert max(r["late"] for r in recs) == pytest.approx(0.0, abs=1e-9)
    assert all(r["due"] < job["t_end"] for r in recs)
