"""A cell's deployment from its data files: the fleet, its seeded history,
its objective and its worker pools.

Pure Python and NumPy, with no JAX: the load generator's process imports
this module too.  Everything here is a function of the configuration, the
traffic mix and ``--seed``; two runs with one seed build the same fleet.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

# the longest window any later check may ask for (``run_seconds`` <= 51)
MAX_WINDOW_S = 51.0
# the stream of the arrival schedule, which is the same for every seed
SCHEDULE = 0


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream per purpose; ``seed`` may exceed 32 bits."""
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


# --------------------------------------------------------------- the space
def sample_params(space: Dict[str, Any], n: int,
                  rng: np.random.Generator) -> List[Dict[str, Any]]:
    """``n`` configurations drawn from a service space spec (the JSON
    grammar of ``POST /studies``: uniform [loc, scale], loguniform [a, b],
    range [start, stop, step], int [lo, hi], logint [lo, hi], choice)."""
    cols = {}
    for name, spec in space.items():
        (kind, arg), = spec.items()
        if kind == "uniform":
            v = (float(arg[0]) + float(arg[1]) * rng.random(n)).tolist()
        elif kind == "loguniform":
            lo, hi = math.log(float(arg[0])), math.log(float(arg[1]))
            v = np.exp(lo + (hi - lo) * rng.random(n)).tolist()
        elif kind == "range":
            choices = list(range(*[int(a) for a in arg]))
            v = [choices[i] for i in rng.integers(0, len(choices), n)]
        elif kind == "int":
            v = rng.integers(int(arg[0]), int(arg[1]) + 1, n).tolist()
        elif kind == "logint":
            lo, hi = math.log(int(arg[0])), math.log(int(arg[1]) + 1)
            v = np.clip(np.floor(np.exp(lo + (hi - lo) * rng.random(n))),
                        int(arg[0]), int(arg[1])).astype(int).tolist()
        elif kind == "choice":
            v = [arg[i] for i in rng.integers(0, len(arg), n)]
        else:
            raise ValueError(f"param {name!r}: unknown spec kind {kind!r}")
        cols[name] = v
    return [{k: cols[k][i] for k in space} for i in range(n)]


# ------------------------------------------------------------ the objective
def objective(spec: Dict[str, Any], p: Dict[str, Any]) -> float:
    """The configuration's closed-form objective: ``base`` plus one term
    per entry of ``terms`` and an offset per categorical value.  A term
    reads one parameter through ``transform`` (``log10`` or ``id``) and
    adds ``weight * (t - center)**2`` (``sq``), ``weight * |t - center|``
    (``abs``) or ``weight * t`` (``lin``)."""
    acc = float(spec["base"])
    for t in spec["terms"]:
        x = float(p[t["param"]])
        x = math.log10(x) if t.get("transform") == "log10" else x
        c = float(t.get("center", 0.0))
        kind, w = t["kind"], float(t["weight"])
        acc += w * ((x - c) ** 2 if kind == "sq" else
                    abs(x - c) if kind == "abs" else x)
    for name, table in spec.get("offsets", {}).items():
        acc += float(table[str(p[name])])
    return acc


# ------------------------------------------------------------------ fleet
def strategies(cfg: Dict[str, Any]) -> List[str]:
    cyc = cfg["strategy_cycle"]
    return [cyc[i % len(cyc)] for i in range(int(cfg["studies"]))]


def study_names(cfg: Dict[str, Any]) -> List[str]:
    return [f"study-{i:02d}" for i in range(int(cfg["studies"]))]


def pool_sizes(mix: Dict[str, Any], studies: int) -> List[int]:
    """Workers per study.  ``zipf``: shares proportional to 1/rank**s over
    the studies in index order, rounded by largest remainder; ``equal``:
    the same pool for every study.  The sizes never depend on the seed."""
    w = int(mix["workers"])
    pools = mix["pools"]
    if pools["kind"] == "equal":
        if w % studies:
            raise ValueError(f"{w} workers do not split over {studies}")
        return [w // studies] * studies
    if pools["kind"] != "zipf":
        raise ValueError(f"unknown pool kind {pools['kind']!r}")
    share = 1.0 / np.arange(1, studies + 1) ** float(pools["s"])
    exact = w * share / share.sum()
    out = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - out), kind="stable")[:w - out.sum()]:
        out[i] += 1
    return out.tolist()


def mean_eval_s(mix: Dict[str, Any]) -> float:
    """E[D] at the mix's nominal rate: ``workers / rate`` asks per second."""
    return int(mix["workers"]) / float(mix["rate"])


def plan_lengths(cfg: Dict[str, Any], mix: Dict[str, Any],
                 seed: int) -> Dict[str, Any]:
    """Seeded observation counts per study, and the bucket ``na`` they
    keep for the whole window.

    A study can gain at most ``pool * ask_n * (1 - fail_share)``
    observations per mean evaluation time, over
    the longest window any check may run plus the lead-in and warm-up.
    The pending cap reaches ``4 * ceil(ask_n * max_pool / 4)``.  ``na`` is
    the bucket that holds the longest study at its end; where no study
    starts in that bucket, the study that gains least is raised to its
    lower edge, so the window starts and ends in one bucket."""
    n = int(cfg["ask_n"])
    names = study_names(cfg)
    pools = pool_sizes(mix, len(names))
    ed = mean_eval_s(mix)
    span = MAX_WINDOW_S + lead_in_s(mix)
    gain = [int(math.ceil(span * p / ed * n * (1.0 - float(mix["fail_share"]))
                          + 2 * n)) for p in pools]
    pend_cap = max(4, -(-n * max(pools) // 4) * 4)
    lspec = cfg["lengths"]
    if lspec["kind"] == "loguniform":
        q = (np.arange(len(names)) + 0.5) / len(names)
        lo, hi = float(lspec["low"]), float(lspec["high"])
        lengths = np.rint(lo * (hi / lo) ** q).astype(int)
        lengths = lengths[rng_for(seed, 1).permutation(len(names))]
        na = _pow2(int(max(lengths + np.array(gain))) + pend_cap + n)
        if lengths.max() + 4 + n <= na // 2:
            # the study that gains least starts at the bucket's lower edge
            # (it takes the longest length first, so that every seed keeps
            # the same set of lengths)
            b = min(range(len(names)), key=lambda i: (gain[i], -lengths[i]))
            top = int(np.argmax(lengths))
            lengths[[b, top]] = lengths[[top, b]]
            lengths[b] = na // 2 - n - 3
            while lengths[b] + gain[b] + pend_cap + n > na:
                na *= 2
                lengths[b] = na // 2 - n - 3
        lengths = lengths.tolist()
    else:
        raise ValueError(f"unknown lengths kind {lspec['kind']!r}")
    ends = [a + g for a, g in zip(lengths, gain)]
    if max(ends) + pend_cap + n > na or max(lengths) + 4 + n <= na // 2:
        raise ValueError(f"lengths {min(lengths)}..{max(lengths)} with gain "
                         f"up to {max(gain)} leave bucket {na}")
    return {"lengths": lengths, "gain": gain, "na": na,
            "pend_cap_max": pend_cap, "pools": pools}


def _pow2(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


def lead_in_s(mix: Dict[str, Any]) -> float:
    """Load offered before the window opens, so that it opens in the
    steady state: one mean evaluation time, at most ten seconds."""
    return min(10.0, mean_eval_s(mix))


def eval_durations(mix: Dict[str, Any],
                   seconds: float) -> List[List[float]]:
    """Each worker's evaluation times: stratified quantiles of the
    lognormal (median set by the rate), enough for the lead-in and the
    window, dealt out to the workers in a fixed order.  They do not depend on the seed: every seed offers the same
    work at the same times, so runs with different seeds differ in the
    fleet's data and not in the load."""
    w = int(mix["workers"])
    sigma = float(mix["eval"]["sigma"])
    median = mean_eval_s(mix) / math.exp(sigma * sigma / 2.0)
    per = int(math.ceil((seconds + lead_in_s(mix)) / mean_eval_s(mix)
                        * 3.0)) + 4
    total = w * per
    q = (np.arange(total) + 0.5) / total
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    d = median * np.exp(sigma * z)
    d = d[rng_for(SCHEDULE, 2).permutation(total)]
    return [d[i::w].tolist() for i in range(w)]


def seeded_history(cfg: Dict[str, Any], lengths: List[int],
                   seed: int) -> List[List[tuple]]:
    """Each study's seeded observations: configurations drawn from the
    space and their objective values with the configuration's noise."""
    out = []
    sd = float(cfg["objective"].get("noise_sd", 0.0))
    for b, k in enumerate(lengths):
        rng = rng_for(seed, 3, b)
        ps = sample_params(cfg["space"], int(k), rng)
        noise = rng.normal(0.0, sd, len(ps)) if sd else np.zeros(len(ps))
        out.append([(p, objective(cfg["objective"], p) + float(e))
                    for p, e in zip(ps, noise)])
    return out
