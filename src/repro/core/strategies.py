"""Parallel batch-selection strategies (paper §2.3): the names a study may
run, the dispatch family each is served by, and the checks on the knobs
a caller may pass for it.

  * ``bayesian`` / ``hallucination`` (default): GP-BUCB (Desautels et al.
    2014) — pick argmax UCB, then *hallucinate* the observation at the
    posterior mean so the variance contracts and the next pick explores a
    different region; served by ``gp.bank_pick``.
  * ``clustering``: (Groves & Pyzer-Knapp 2018) — compute the acquisition
    surface on the MC candidates, keep the top quantile, k-means it into
    ``batch_size`` spatially distinct clusters, return each cluster's
    argmax; served by ``gp.bank_cluster_pick``.
  * ``tpe``: the Hyperopt baseline, served by
    ``tpe.fused_tpe_propose_bank``.
  * ``random``: batch of valid random samples (the paper's third
    optimizer), drawn on the host by ``random_picks``.

Every GP, clustering and TPE ask runs through ``StudyBank``'s staged
device pipeline; ``bench/lib/reference.py`` holds the float64 references
the picks are judged against.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

# strategy name -> dispatch family.  "gp" and "cluster" share the bank's
# observation stages (fit, factors) and differ only in the pick head;
# "tpe" has its own buffer layout; "random" rows draw on the host.
STRATEGIES = {
    "bayesian": "gp",        # mango's default name
    "hallucination": "gp",
    "clustering": "cluster",
    "tpe": "tpe",
    "random": "random",
}

# the strategy_kwargs each family accepts; "random" takes (and ignores)
# anything
_KWARGS = {"gp": (), "cluster": ("top_frac",),
           "tpe": ("gamma", "pending_penalty")}


def check_name(name: str) -> str:
    """``name``'s dispatch family; an unknown name raises ``ValueError``."""
    if name not in STRATEGIES:
        raise ValueError(f"unknown optimizer {name!r}; "
                         f"choose from {sorted(STRATEGIES)}")
    return STRATEGIES[name]


def check_kwargs(name: str, kwargs: Dict[str, Any], dim: int,
                 domain_size: float) -> None:
    """Refuse ``strategy_kwargs`` that strategy ``name`` cannot run with:
    ``TypeError`` on a key its family does not take, ``ValueError`` on a
    value out of range."""
    fam = check_name(name)
    if fam == "random":
        return
    unknown = sorted(set(kwargs) - set(_KWARGS[fam]))
    if unknown:
        raise TypeError(f"optimizer {name!r} got unexpected "
                        f"strategy_kwargs {unknown}")
    if fam == "tpe":
        gamma = kwargs.get("gamma", 0.25)
        if dim < 1:
            raise ValueError(f"TPE needs dim >= 1, got {dim}")
        # gamma is the GOOD quantile; > 0.5 would make the "good" model
        # the majority (nonsensical for TPE) and is what lets the fused
        # program score both splits with one exp per row (disjoint splits)
        if not 0.0 < gamma <= 0.5:
            raise ValueError(f"gamma must be in (0, 0.5], got {gamma}")
        if not domain_size > 0:
            raise ValueError(f"domain_size must be > 0, got {domain_size}")


def n_top_candidates(S: int, batch_size: int, top_frac: float) -> int:
    """Top-quantile size for the clustering pick head (static in
    ``bank_cluster_pick``)."""
    return min(max(batch_size * 4, int(S * top_frac)), S)


def random_picks(n_cands: int, batch_size: int, seed: int) -> List[int]:
    """Indices of a random batch among ``n_cands`` candidates.  Clamped: a
    small mc_samples override can leave fewer candidates than batch slots
    — return what exists instead of raising."""
    rng = np.random.default_rng(seed)
    return list(rng.choice(n_cands, size=min(batch_size, n_cands),
                           replace=False))
