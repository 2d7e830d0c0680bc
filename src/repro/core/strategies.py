"""Parallel batch-selection strategies (paper §2.3).

  * ``bayesian`` (default): the fused GP-BUCB path — one jit'd device
    program per batch (``gp.fused_propose``) on top of incremental O(n^2)
    Cholesky observation appends.
  * ``hallucination_ref``: Batched GP Bandits / GP-BUCB (Desautels et al.
    2014) as a numpy-facing Python loop — sequentially pick argmax UCB, then
    *hallucinate* the observation at the posterior mean so the variance
    contracts and the next pick explores a different region.  Kept as the
    reference implementation the fused path is tested against.
  * ``clustering``: (Groves & Pyzer-Knapp 2018) — compute the acquisition
    surface on the MC candidates, keep the top quantile, k-means it into
    ``batch_size`` spatially distinct clusters, return each cluster's argmax.
  * ``random``: batch of valid random samples (the paper's third optimizer).

All strategies consume an *encoded* candidate matrix sampled from the native
parameter distributions, so every proposed configuration is valid (discrete /
categorical parameters included).
"""
from __future__ import annotations

import warnings
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import gp as gp_lib
from repro.core import scoring
from repro.core.acquisition import adaptive_beta, ucb
from repro.core.gp import GaussianProcess
from repro.core.kmeans import kmeans_assign

SCORERS = ("chol", "kinv_jnp", "kinv_pallas")


def n_top_candidates(S: int, batch_size: int, top_frac: float) -> int:
    """Top-quantile size for the clustering pipeline.  Module-level so the
    StudyBank's batched clustering ask computes the exact same (static)
    ``n_top`` as the single-study strategy."""
    return min(max(batch_size * 4, int(S * top_frac)), S)


class BaseStrategy:
    """A strategy consumes encoded observations + candidates and returns
    pick indices.  ``propose`` additionally accepts ``pending`` — the
    encoded configurations of trials currently in flight (the ask/tell
    core's ledger) — which GP strategies hallucinate (GP-BUCB semantics:
    variance contraction, no mean update) before picking.

    ``scorer`` selects the GP scoring backend:

      * ``"chol"`` (default) — the L-based fused path (``gp.fused_propose``),
      * ``"kinv_pallas"`` — the shared conditioning-hardened factor core
        through the ``gp_acquisition`` Pallas kernels (what
        ``use_pallas=True`` resolves to),
      * ``"kinv_jnp"`` — the same core executed as the kernels' jnp oracle
        twin (the parity path the 3-way near-tie tests drive).

    Every propose through a fitted GP also stages ``last_cond_proxy`` — a
    host-visible condition-number estimate for K (power iteration on
    K and K^{-1} through the Cholesky factor, ``scoring.cond_estimate``;
    typically within ~2x of ``numpy.linalg.cond``, where the old
    Cholesky-diagonal bound sat 20-50x low), computed lazily on access
    (reading it costs one small device program + sync; not reading it
    costs nothing); above ``scoring.COND_PROXY_WARN`` a one-time warning
    fires on access (float32 posterior scoring is presumed unreliable
    there).
    """

    needs_gp = True

    def __init__(self, dim: int, domain_size: float, fit_steps: int = 40,
                 use_pallas: bool = False,
                 refit_every: int = 8, scorer: Optional[str] = None):
        self._scorer_explicit = scorer is not None
        if scorer is None:
            scorer = "kinv_pallas" if use_pallas else "chol"
        elif scorer not in SCORERS:
            raise ValueError(f"unknown scorer {scorer!r}; "
                             f"choose from {SCORERS}")
        elif use_pallas and scorer != "kinv_pallas":
            # contradictory request: raise like every other invalid config
            # instead of silently dropping one of the two flags
            raise ValueError(f"use_pallas=True conflicts with "
                             f"scorer={scorer!r} (the Pallas kernels are "
                             f"scorer='kinv_pallas')")
        self.scorer = scorer
        self.use_pallas = scorer == "kinv_pallas"
        self.gp = GaussianProcess(dim, fit_steps=fit_steps,
                                  refit_every=refit_every,
                                  track_factor=scorer != "chol")
        self.domain_size = domain_size
        self._cond_src = None
        self._cond_warned = False

    def _update_cond_proxy(self, st, na: Optional[int] = None) -> None:
        """Stage the conditioning diagnostic for the active window (the
        proxy itself is computed lazily on ``last_cond_proxy`` access, so
        an ask that never reads it pays no extra device dispatch or host
        sync — the one-device-program-per-ask contract holds)."""
        self._cond_src = (st.L, st.mask, na)

    @property
    def last_cond_proxy(self) -> Optional[float]:
        """Condition-number estimate for the last propose's active kernel
        window (None before the first GP-backed propose)."""
        if self._cond_src is None:
            return None
        L, m, na = self._cond_src
        if na is not None:
            L, m = L[:na, :na], m[:na]
        val = float(scoring.cond_estimate(L, jnp.asarray(m)))
        if val > scoring.COND_PROXY_WARN and not self._cond_warned:
            self._cond_warned = True
            warnings.warn(
                f"GP kernel condition estimate {val:.2e} exceeds "
                f"{scoring.COND_PROXY_WARN:.0e}: float32 posterior scores "
                "may be unreliable (consider a larger noise floor, or "
                "enabling x64 for float64 Schur accumulation)",
                RuntimeWarning, stacklevel=2)
        return val

    def _predict(self, st, C: np.ndarray):
        if self.use_pallas:
            from repro.kernels.gp_acquisition import ops as gp_ops
            return gp_ops.gp_mean_std(st, C)
        return self.gp.predict(C, st)

    def _absorb_pending(self, st, pending):
        """Host-loop fallback: hallucinate in-flight rows one by one."""
        st = self.gp.ensure_capacity(st, len(pending))
        for p in np.asarray(pending, dtype=np.float32):
            st = self.gp.hallucinate(st, p)
        return st

    def propose(self, X: np.ndarray, y: np.ndarray, candidates: np.ndarray,
                batch_size: int, seed: int = 0,
                pending: Optional[np.ndarray] = None) -> List[int]:
        raise NotImplementedError


class HallucinationStrategy(BaseStrategy):
    def propose(self, X, y, candidates, batch_size, seed=0, pending=None):
        st = self.gp.fit(X, y)
        n_pend = 0 if pending is None else len(pending)
        if n_pend:
            st = self._absorb_pending(st, pending)
        n_evals = len(y) + n_pend
        picked: List[int] = []
        avail = np.ones(len(candidates), dtype=bool)
        for b in range(batch_size):
            mu, sd = self._predict(st, candidates)
            beta = adaptive_beta(n_evals, self.domain_size, batch_index=b)
            acq = ucb(mu, sd, beta)
            acq[~avail] = -np.inf
            idx = int(np.argmax(acq))
            picked.append(idx)
            avail[idx] = False
            if b + 1 < batch_size:
                st = self.gp.hallucinate(st, candidates[idx])
        return picked


class FusedHallucinationStrategy(BaseStrategy):
    """GP-BUCB on the fused device-resident hot path (the default).

    Observations are absorbed incrementally (O(n^2) Cholesky appends, full
    hyperparameter refit every ``refit_every`` new points) and the whole
    batch loop runs as a single jit'd ``lax.fori_loop`` — picks identical
    candidate indices to ``HallucinationStrategy`` on fixed seeds.
    """

    def propose(self, X, y, candidates, batch_size, seed=0, pending=None):
        n_pend = 0 if pending is None else len(pending)
        st = self.gp.observe(X, y)
        st = self.gp.ensure_capacity(st, batch_size + n_pend)
        return self.pick_from_state(st, candidates, batch_size,
                                    pending=pending)

    def pick_from_state(self, st, candidates, batch_size, pending=None):
        """Window + dispatch the fused program against an explicit state.

        ``pending`` (encoded in-flight rows) rides along into the device
        program: ``fused_propose_pending`` (or, on the factor-core scorer
        paths, ``fused_propose_pallas_pending`` with the shared hardened
        ``scoring.absorb_pending`` loop) hallucinates them inside the jit'd
        fori_loop, so an async replacement pick is exactly one GP program
        dispatch on *every* path.
        """
        n_pend = 0 if pending is None else len(pending)
        # active window: a 64-multiple slice covering n + pending +
        # batch_size rows.  The leading principal block of L is the Cholesky
        # of the leading block of K (and of L^{-1} the inverse of that
        # block), so slicing is exact — it just avoids paying the
        # power-of-two padded size (up to 2n) in the O(n^2 S) posterior.
        n_pad = st.X.shape[0]
        na = min(n_pad, max(16,
                            -(-(st.n + n_pend + batch_size) // 64) * 64))
        self._update_cond_proxy(st, na)
        C = jnp.asarray(np.ascontiguousarray(candidates, dtype=np.float32))
        args = (jnp.asarray(st.X[:na]), jnp.asarray(st.y[:na]),
                jnp.asarray(st.mask[:na]))
        tail = (C, st.ls, st.var, st.noise, jnp.int32(st.n),
                jnp.float32(self.domain_size))
        if n_pend:
            # pad the pending buffer to a small static cap so the jit cache
            # sees a handful of shapes, not one per in-flight count
            cap = -(-n_pend // 4) * 4
            P = np.zeros((cap, st.X.shape[1]), np.float32)
            P[:n_pend] = np.asarray(pending, dtype=np.float32)
        if self.scorer != "chol" and n_pend:
            picks = gp_lib.fused_propose_pallas_pending(
                *args, st.L[:na, :na], st.Linv[:na, :na],
                jnp.asarray(P), jnp.int32(n_pend), *tail,
                batch_size=batch_size, pend_cap=cap,
                use_pallas=self.use_pallas)
        elif self.scorer != "chol":
            picks = gp_lib.fused_propose_pallas(
                *args, st.L[:na, :na], st.Linv[:na, :na], *tail,
                batch_size=batch_size,
                use_pallas=self.use_pallas)
        elif n_pend:
            picks = gp_lib.fused_propose_pending(
                args[0], args[1], args[2], st.L[:na, :na],
                jnp.asarray(P), jnp.int32(n_pend), *tail,
                batch_size=batch_size, pend_cap=cap)
        else:
            picks = gp_lib.fused_propose(*args, st.L[:na, :na], *tail,
                                         batch_size=batch_size)
        return [int(i) for i in np.asarray(picks)]


class ClusteringStrategy(BaseStrategy):
    """Groves & Pyzer-Knapp 2018 batch selection, fully on-device.

    ``propose`` dispatches ``acquisition.fused_cluster_propose`` — pending
    absorb, posterior + UCB, ``lax.top_k``, weighted k-means, and the
    per-cluster argmax all run inside one jit'd program; the (n_mc,)
    acquisition surface never reaches the host.  Scoring and pending
    absorption go through the shared conditioning-hardened factor core
    (``core.scoring``) — the same backend as the fused GP-BUCB path, with
    ``use_pallas`` selecting the ``gp_acquisition`` kernels and the default
    running their jnp twin.  ``propose_host`` keeps the numpy pipeline as
    the parity reference (with the empty-cluster backfill fixed to never
    re-select an already-picked index).
    """

    def __init__(self, *args, top_frac: float = 0.2, **kwargs):
        super().__init__(*args, **kwargs)
        if self.scorer == "chol":
            if self._scorer_explicit:
                # an explicitly requested L-path scorer cannot be honored:
                # raise instead of silently substituting a backend
                raise ValueError(
                    "ClusteringStrategy scores through the shared factor "
                    "core; scorer must be 'kinv_jnp' or 'kinv_pallas'")
            # default: the shared factor core's jnp backend — the L-based
            # posterior clustering used before ISSUE 5 was a second,
            # divergent scoring backend
            self.scorer = "kinv_jnp"
            self.gp.track_factor = True
        self.top_frac = top_frac

    def _n_top(self, S: int, batch_size: int) -> int:
        return n_top_candidates(S, batch_size, self.top_frac)

    def propose(self, X, y, candidates, batch_size, seed=0, pending=None):
        import jax

        from repro.core.acquisition import fused_cluster_propose

        S = len(candidates)
        batch_size = min(batch_size, S)
        st = self.gp.observe(X, y)
        n_pend = 0 if pending is None else len(pending)
        st = self.gp.ensure_capacity(st, n_pend)
        # pad the pending buffer to a small static cap (>= 4 so the no-
        # pending trace never indexes an empty buffer)
        cap = max(4, -(-n_pend // 4) * 4)
        P = np.zeros((cap, st.X.shape[1]), np.float32)
        if n_pend:
            P[:n_pend] = np.asarray(pending, dtype=np.float32)
        n_pad = st.X.shape[0]
        na = min(n_pad, max(16, -(-(st.n + n_pend) // 64) * 64))
        self._update_cond_proxy(st, na)
        picks = fused_cluster_propose(
            jnp.asarray(st.X[:na]), jnp.asarray(st.y[:na]),
            jnp.asarray(st.mask[:na]), st.L[:na, :na], st.Linv[:na, :na],
            jnp.asarray(P), jnp.int32(n_pend),
            jnp.asarray(np.ascontiguousarray(candidates, dtype=np.float32)),
            st.ls, st.var, st.noise, jnp.int32(st.n),
            jnp.float32(self.domain_size), jax.random.PRNGKey(seed),
            batch_size=batch_size, n_top=self._n_top(S, batch_size),
            pend_cap=cap, use_pallas=self.use_pallas)
        return [int(i) for i in np.asarray(picks)]

    def propose_host(self, X, y, candidates, batch_size, seed=0,
                     pending=None):
        """Numpy reference pipeline (the parity oracle for the device
        program): standardized acquisition surface, descending-sorted top
        slice, host k-means, per-cluster argmax excluding prior picks."""
        import jax

        batch_size = min(batch_size, len(candidates))
        st = self.gp.observe(X, y)
        n_pend = 0 if pending is None else len(pending)
        if n_pend:
            st = self._absorb_pending(st, pending)
        mu, var_s = gp_lib.posterior(
            jnp.asarray(st.X), jnp.asarray(st.y), jnp.asarray(st.mask),
            st.L, jnp.asarray(candidates, dtype=jnp.float32),
            st.ls, st.var, st.noise)
        mu, sd = np.asarray(mu), np.sqrt(np.asarray(var_s))
        beta = adaptive_beta(len(y) + n_pend, self.domain_size)
        acq = ucb(mu, sd, beta)
        if batch_size == 1:
            return [int(np.argmax(acq))]
        n_top = self._n_top(len(candidates), batch_size)
        top = np.argsort(-acq, kind="stable")[:n_top]
        w = acq[top] - acq[top].min() + 1e-6
        assign = kmeans_assign(candidates[top], w, batch_size, seed=seed)
        picked: List[int] = []
        for c in range(batch_size):
            members = top[assign == c]
            members = members[~np.isin(members, picked)]
            if len(members) == 0:   # empty cluster: back-fill from the
                members = top[~np.isin(top, picked)]   # unpicked remainder
            if len(members) == 0:
                break
            picked.append(int(members[np.argmax(acq[members])]))
        return picked


class RandomStrategy(BaseStrategy):
    needs_gp = False

    def __init__(self, dim: int = 0, domain_size: float = 1.0, **kwargs):
        pass

    def propose(self, X, y, candidates, batch_size, seed=0, pending=None):
        rng = np.random.default_rng(seed)
        # clamp: a small mc_samples override can leave fewer candidates
        # than batch slots — return what exists instead of raising
        return list(rng.choice(len(candidates),
                               size=min(batch_size, len(candidates)),
                               replace=False))


STRATEGIES = {
    "bayesian": FusedHallucinationStrategy,     # mango's default name
    "hallucination": FusedHallucinationStrategy,
    "hallucination_ref": HallucinationStrategy,  # numpy reference path
    "clustering": ClusteringStrategy,
    "random": RandomStrategy,
}
