"""The ``lcbench_long8`` deployment on the CPU: its plan keeps one bucket
at the cell's rates, the program's fit over its log-scaled space matches
the float64 reference, and a tiny GP-only cell over the same space runs
whole: sound, it is correct; with a planted fault, or under the control,
it is not."""
import contextlib
import json
import math
import time
from pathlib import Path

import numpy as np
import pytest

from bench.lib import deployment as dep
from bench.lib import reference as ref
from bench.lib.cell import run_cell
from bench.lib.registry import Registry
from bench.tests import tiny

BENCH = Path(__file__).resolve().parents[1]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cfg():
    return json.loads((BENCH / "configs" / "lcbench_long8.json").read_text())


def _mix():
    return json.loads((BENCH / "traffic" / "lcbench8_steady.json")
                      .read_text())


@pytest.mark.parametrize("share", [0.5, 1.0, 1.25])
def test_plan_keeps_bucket_2048(share):
    """At the cell's rate and up to a quarter above it, every study's
    seeded history and its gain over the longest window stay in the 2048
    bucket, and the longest starts in its upper half."""
    c, m = _cfg(), _mix()
    m = dict(m, rate=share * float(m["rate"]))
    plan = dep.plan_lengths(c, m, 2**31 + 15)
    n, na = c["ask_n"], plan["na"]
    assert na == 2048
    assert max(plan["lengths"]) + 4 + n > na // 2
    ends = [a + g for a, g in zip(plan["lengths"], plan["gain"])]
    assert max(ends) + plan["pend_cap_max"] + n <= na
    assert plan["pools"] == [8] * 8


def _space():
    from repro.core.spaces import ParamSpace
    from repro.service.server import space_from_spec
    return ParamSpace(space_from_spec(_cfg()["space"]))


def test_space_is_seven_dims_of_the_stated_kinds():
    sp = _space()
    assert sp.dim == _cfg()["encoded_dims"] == 7
    assert sp.mc_samples(_cfg()["ask_n"]) == _cfg()["candidates_per_ask"]
    rows = dep.sample_params(_cfg()["space"], 500,
                             np.random.default_rng(3))
    X = np.asarray(sp.encode(rows))
    assert X.shape == (500, 7) and X.min() >= 0.0 and X.max() <= 1.0
    bs = [r["batch_size"] for r in rows]
    assert min(bs) >= 16 and max(bs) <= 512
    assert all(isinstance(r["num_layers"], int) for r in rows)
    # log-scaled: the median batch size lies near the geometric mean
    assert 60 < float(np.median(bs)) < 130


def test_one_fit_on_the_log_scaled_space_matches_the_reference():
    """The program's fit (float32 on the CPU at any precision) over a
    seeded LCBench history reaches the reference's hyperparameters."""
    import jax.numpy as jnp

    from repro.core import gp
    hist = dep.seeded_history(_cfg(), [120], 2**31 + 7)[0]
    X = np.asarray(_space().encode([p for p, _ in hist]), np.float64)
    y = np.array([v for _, v in hist])
    d = X.shape[1]
    ym, ys = float(y.mean()), float(y.std()) + 1e-6
    cold = np.array([ref.COLD_LOG_LS] * d + [ref.COLD_LOG_VAR,
                                             ref.COLD_LOG_NOISE])
    mine = ref.adam_fit(cold, X, (y - ym) / ys, 40)
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


# ----------------------------------------------------- a tiny whole cell
# two GP-BUCB studies over the LCBench space: 40-60 observations, a batch
# of 2 and one worker in two per study at 1 ask/s keep the bucket at 128
CONFIG = dict(_cfg(), name="tiny_lcbench", studies=2, ask_n=2,
              lengths={"kind": "loguniform", "low": 40, "high": 60},
              service={"fit_steps": 10, "refit_every": 8,
                       "compact_every_ops": 0, "mc_samples": 64})
MIX = {"kind": "worker_pools", "workers": 4, "pools": {"kind": "equal"},
       "rate": 1.0, "eval": {"dist": "lognormal", "sigma": 1.0},
       "fail_share": 0.1}
LIMITS = {"sample": {"gp": 2},
          "limits": {"fit_gap": 1e-3, "fit_count_mismatch": 0,
                     "gp_pick_gap": 1e-3, "gp_pick_gap_mean": 1e-4}}
CELL = "tiny_lcbench.steady"


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    root = tiny.make(tmp_path_factory.mktemp("root"))
    (root / "bench/configs/tiny_lcbench.json").write_text(json.dumps(CONFIG))
    (root / "bench/traffic/tiny_lcbench_mix.json").write_text(
        json.dumps(MIX))
    (root / f"bench/limits/{CELL}.json").write_text(json.dumps(LIMITS))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny_lcbench", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "bench/configs/tiny_lcbench.json"})
    b["workloads"].append({"name": CELL, "config": "tiny_lcbench",
                           "traffic": "tiny_lcbench_mix", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "lcbench8.steady" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return Registry(root)


@contextlib.contextmanager
def bf16_products():
    """The control's effect, as the CPU can show it.  On the TPU the
    control runs each float32 product of the GP programs as one bfloat16
    pass: operands rounded to bfloat16, sums kept in float32.  On the CPU
    a float32 product is float32 at any precision, so the control alone
    changes no number here; while this is open, every float32 product
    that a program traces takes bfloat16 operands, as that pass does."""
    import jax
    import jax.numpy as jnp
    from jax._src.lax import lax

    orig = lax.dot_general

    def one_pass(lhs, rhs, dimension_numbers, precision=None,
                 preferred_element_type=None, **kw):
        if lhs.dtype == jnp.float32 and rhs.dtype == jnp.float32:
            lhs, rhs = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
            preferred_element_type = jnp.float32
        return orig(lhs, rhs, dimension_numbers, precision=precision,
                    preferred_element_type=preferred_element_type, **kw)

    lax.dot_general = one_pass
    try:
        yield
    finally:
        lax.dot_general = orig
        jax.clear_caches()


def _run(reg, tmp, fault=None, control=False, seed=2**31 + 4015):
    return run_cell(reg, CELL, seed, 8.0, False, device=dict(CPU),
                    t_process=time.monotonic(), out=Path(tmp), fault=fault,
                    control=control)


def _over(res, names):
    return [k for k in names
            if (res["checks"][k]["value"] or 0) > res["checks"][k]["limit"]]


def test_tiny_cell_sound_run_is_correct(reg, tmp_path):
    res = _run(reg, tmp_path)
    bad = {k: c for k, c in res["checks"].items()
           if c["value"] is None or c["value"] > c["limit"]}
    assert res["correct"] and not bad, bad
    assert set(LIMITS["limits"]) <= set(res["checks"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["bucket_moved"]["value"] == 0
    assert set(res["metrics"]) == {"ask_p90_ms", "setup_s"}


def test_tiny_cell_planted_fault_is_caught(reg, tmp_path):
    res = _run(reg, tmp_path, fault="answer")
    assert not res["correct"], res["checks"]
    assert _over(res, ("gp_pick_gap", "gp_pick_gap_mean")), res["checks"]


def test_tiny_cell_control_is_caught(reg, tmp_path):
    with bf16_products():
        res = _run(reg, tmp_path, control=True)
    assert not res["correct"], res["checks"]
    assert _over(res, ("fit_gap", "gp_pick_gap", "gp_pick_gap_mean")), \
        res["checks"]
