"""UCB acquisition with Mango's adaptive exploration/exploitation schedule,
and the fused device-side clustering proposal built on top of it.

beta follows the GP-UCB schedule (Srinivas et al.), scaled — as the paper
describes — by search-space size, completed evaluations, and the position
within the parallel batch (GP-BUCB increments t per hallucinated pick):

    beta_t = 2 * log(domain_size * t^2 * pi^2 / (6 * delta))

``fused_cluster_propose`` is the clustering strategy's (Groves &
Pyzer-Knapp 2018) whole pipeline as one jit'd device program: pending-trial
absorb -> posterior + UCB -> ``jax.lax.top_k`` -> weighted k-means
(``kmeans._kmeans``) -> per-cluster argmax.  Only the ``(batch_size,)``
pick indices ever leave the device — the (n_mc,) acquisition surface and
the top-quantile slice stay on it.  Scoring and pending absorption run
through ``core.scoring`` — the same conditioning-hardened core (and, with
``use_pallas``, the same ``gp_acquisition`` kernels) as
``gp.fused_propose_pallas_pending``, so there is exactly one GP scoring
backend in the tree.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import _kmeans


def adaptive_beta(n_evals: int, domain_size: float, batch_index: int = 0,
                  delta: float = 0.1) -> float:
    t = max(n_evals + batch_index, 1)
    beta = 2.0 * math.log(
        max(domain_size, 2.0) * t * t * math.pi ** 2 / (6.0 * delta))
    return float(np.clip(beta, 1.0, 100.0))


def ucb(mu: np.ndarray, sigma: np.ndarray, beta: float) -> np.ndarray:
    return mu + math.sqrt(beta) * sigma


@functools.partial(jax.jit, static_argnames=("batch_size", "n_top",
                                             "pend_cap", "use_pallas",
                                             "block_s"))
def fused_cluster_propose(X: jax.Array, y: jax.Array, mask: jax.Array,
                          L: jax.Array, Linv: jax.Array, P: jax.Array,
                          n_pending: jax.Array,
                          C: jax.Array, ls, var, noise, n_obs: jax.Array,
                          domain_size: jax.Array, key,
                          batch_size: int, n_top: int,
                          pend_cap: int, use_pallas: bool = False,
                          block_s: int = 256) -> jax.Array:
    """Device-resident clustering batch proposal: one program per ask.

    1. Absorb the (padded, ``pend_cap``) pending buffer through the shared
       core's hardened absorb loop (``scoring.absorb_pending``) — posterior
       mean at each in-flight point, rank-1 (L, Linv) factor append
       (GP-BUCB semantics), exactly the loop the fused Pallas proposal
       runs.
    2. Posterior + adaptive-beta UCB over all candidates through the one
       shared scorer (``scoring.posterior_scores`` — the Pallas
       ``gp_acquisition`` kernel when ``use_pallas``, its jnp twin
       otherwise; standardized y space — the de-standardized surface
       differs by a positive affine map, so top-k and argmax are
       identical).
    3. ``jax.lax.top_k`` keeps the ``n_top`` best; their scores (shifted to
       positive) weight the k-means.
    4. Weighted k-means (k-means++ seeding + Lloyd, ``kmeans._kmeans``)
       splits the top set into ``batch_size`` spatial clusters.
    5. Each cluster contributes its acquisition argmax; already-picked
       points are excluded *before* each cluster's argmax and empty
       clusters back-fill from the unpicked remainder of the top set, so
       the batch is unique by construction (the host implementation's
       post-hoc dedupe could silently collapse spatial diversity).
    """
    from repro.core import scoring

    S = C.shape[0]
    Xs, Cs = scoring.prescale(X, C, ls, block_s)
    dp = Xs.shape[1]
    d = X.shape[1]
    Ps = jnp.zeros((pend_cap, dp), jnp.float32).at[:, :d].set(P / ls)
    Xs, y, mask, L, Linv = scoring.absorb_pending(
        Xs, y, mask, L, Linv, Ps, n_pending, n_obs, var, noise, pend_cap)

    mu, sig2, _, _ = scoring.posterior_scores(
        Cs, Xs, y, mask, Linv, var, noise, use_pallas=use_pallas,
        block_s=block_s)
    beta = scoring.adaptive_beta_dev(n_obs + n_pending, domain_size)
    acq = mu + jnp.sqrt(beta) * jnp.sqrt(sig2)
    acq = jnp.where(jnp.arange(Cs.shape[0]) < S, acq, -jnp.inf)

    top_vals, top_idx = jax.lax.top_k(acq, n_top)
    w = top_vals - top_vals[n_top - 1] + 1e-6
    assign = _kmeans(C[top_idx], w, key, batch_size)

    def body(c, carry):
        picked, picks = carry
        in_c = (assign == c) & ~picked
        sel = jnp.where(jnp.any(in_c), in_c, ~picked)   # empty-cluster fill
        vals = jnp.where(sel, top_vals, -jnp.inf)
        j = jnp.argmax(vals).astype(jnp.int32)
        return picked.at[j].set(True), picks.at[c].set(top_idx[j])

    _, picks = jax.lax.fori_loop(
        0, batch_size, body,
        (jnp.zeros((n_top,), bool), jnp.zeros((batch_size,), jnp.int32)))
    return picks


# NOTE: the monolithic ``fused_cluster_propose_bank`` (which refactored the
# factors in-program per ask) is gone — clustering fleets now ride the
# bank's STAGED pipeline (``gp.bank_factors``/``bank_dist``/``bank_exp``
# feeding ``gp.bank_cluster_pick``), sharing the obs-stamp cache with the
# GP-BUCB rows instead of recomputing every study's Cholesky every ask.
