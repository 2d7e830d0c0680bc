"""Tree-structured Parzen Estimator baseline (the Hyperopt algorithm),
device-resident.

The paper's evaluation compares Mango against Hyperopt; hyperopt is not
installable offline, so we reimplement its TPE core faithfully enough for
the comparison:

  * split observations into good/bad by the gamma-quantile of y,
  * model each encoded dimension with 1D Parzen windows (Gaussian KDE with
    a per-dimension bandwidth: Scott base scaled by each dim's split
    spread, so one-hot-encoded categoricals — whose 0/1 support a d-global
    rule oversmooths — act as a sharper smoothed frequency estimate),
  * score candidates by l(x)/g(x) (expected-improvement surrogate) and take
    the top of the Monte-Carlo candidate set,
  * parallel batches take the top-b scores (Hyperopt's naive parallelism —
    no information-gain machinery, which is exactly the gap Mango's
    hallucination/clustering strategies target).

As of ISSUE 4 the whole proposal is ONE jit'd device program per ask
(``fused_tpe_propose``, mirroring ``gp.fused_propose_pallas_pending``): the
good/bad split runs as masked ranks over the padded observation buffer, the
O(m n d) product-Parzen scorer is either the pure-jnp oracle or the
``kernels/tpe_kde`` Pallas kernel (``use_pallas=True``), and the batch is
selected with ``lax.top_k`` — only the (batch_size,) pick indices leave the
device.  The seed numpy loop is kept as ``propose_host``, the parity oracle.

Pending trials: Hyperopt's parallelism is *naive* — in-flight trials are
ignored, so an async replacement pick degenerates to re-proposing the
current top-b.  ``pending_penalty=True`` (opt-in, off by default to keep
baseline semantics) hallucinates the in-flight configurations into the
*bad*-split KDE ("pessimistic liar"): g(x) rises around pending points, so
replacement picks steer away from duplicating work already in flight.  The
absorb is just one extra membership mask over the same buffer — still one
device program per ask, no matter how many trials are outstanding.

Registered as ``optimizer="tpe"`` so every Tuner feature (schedulers, fault
tolerance, checkpointing) applies to the baseline too.  ``propose`` is
stateless — it never mutates strategy or shared buffers, so concurrent
drivers can share one instance.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.strategies import STRATEGIES, BaseStrategy
from repro.kernels.tpe_kde.ops import pad_dims, pad_rows
from repro.kernels.tpe_kde.ref import scott_bandwidth, tpe_scores_ref
from repro.kernels.tpe_kde.tpe_kde import tpe_scores_pallas


@functools.partial(jax.jit, static_argnames=(
    "batch_size", "d_true", "use_pallas", "block_s"))
def fused_tpe_propose(X, y, C, meta, *, batch_size: int, d_true: int,
                      use_pallas: bool = False,
                      block_s: int = 256):
    """One device program per ask: split -> l/g scoring -> ``lax.top_k``.

    X (na, dp) is the padded buffer of observed rows followed by pending
    rows (the penalty's in-flight set, empty unless enabled) and zero
    padding, in that order; y (na,) carries the observed objective values.
    C (Sp, dp) are the padded Monte-Carlo candidates.  ``meta`` packs the
    four scalars [n_obs, n_pend, n_cand, gamma] as one f32 row — one
    host->device transfer instead of six; every row mask is derived from it
    in-program.  Returns the (batch_size,) pick indices.
    """
    n_obs = meta[0].astype(jnp.int32)
    n_pend = meta[1].astype(jnp.int32)
    n_cand = meta[2].astype(jnp.int32)
    gamma = meta[3]
    row = jnp.arange(X.shape[0], dtype=jnp.int32)
    is_obs = row < n_obs
    pend_mask = ((row >= n_obs) & (row < n_obs + n_pend)) \
        .astype(jnp.float32)
    # rank observed rows best-first (stable, like the host argsort)
    neg = jnp.where(is_obs, -y, jnp.inf)
    order = jnp.argsort(neg)
    rank = jnp.zeros_like(row).at[order].set(row)
    # split count in float32 on BOTH paths so ceil ties can't flip vs host
    n_good = jnp.maximum(
        1, jnp.ceil(gamma * n_obs.astype(jnp.float32))).astype(jnp.int32)
    good = (rank < n_good) & is_obs
    wg = good.astype(jnp.float32)
    wb_obs = ((rank >= n_good) & is_obs).astype(jnp.float32)
    wb_obs = jnp.where(n_obs > n_good, wb_obs, wg)   # empty bad -> good
    wb = jnp.minimum(wb_obs + pend_mask, 1.0)        # pessimistic liar
    ng = jnp.sum(wg)
    nb = jnp.sum(wb)
    # per-DIM bandwidths: Scott base scaled by each split's per-dim spread
    # (clipped 2*std), so low-variance dims — categorical one-hot columns
    # especially — get a sharper kernel than the d-global rule's
    Xd = X[:, :d_true]
    mg = (wg @ Xd) / jnp.maximum(ng, 1.0)                     # (d,)
    vg = (wg @ (Xd - mg) ** 2) / jnp.maximum(ng, 1.0)
    mb = (wb @ Xd) / jnp.maximum(nb, 1.0)
    vb = (wb @ (Xd - mb) ** 2) / jnp.maximum(nb, 1.0)
    bw_g = scott_bandwidth(ng, d_true) \
        * jnp.clip(2.0 * jnp.sqrt(vg), 0.1, 1.0)             # (d,)
    bw_b = scott_bandwidth(nb, d_true) \
        * jnp.clip(2.0 * jnp.sqrt(vb), 0.1, 1.0)
    # per-row per-dim bandwidth scale: gamma <= 0.5 keeps the splits
    # disjoint, so each row carries its own split's 1/(2 bw_j^2) vector and
    # one exp per (candidate, row, dim) feeds both densities
    a = jnp.zeros(X.shape, jnp.float32).at[:, :d_true].set(
        jnp.where(good[:, None], (0.5 / (bw_g * bw_g))[None, :],
                  (0.5 / (bw_b * bw_b))[None, :]))
    scal = jnp.stack([1.0 / ng, 1.0 / nb, jnp.float32(0.0),
                      jnp.float32(0.0)])[None, :]
    if use_pallas:
        score = tpe_scores_pallas(C, X, a, wg, wb, scal, d_true=d_true,
                                  block_s=block_s)
    else:
        score = tpe_scores_ref(C, X, a, wg, wb, scal, d_true=d_true)
    score = jnp.where(jnp.arange(C.shape[0]) < n_cand, score, -jnp.inf)
    _, idx = jax.lax.top_k(score, batch_size)
    return idx


@functools.partial(jax.jit, static_argnames=(
    "batch_size", "d_true", "use_pallas", "block_s"))
def fused_tpe_propose_bank(X, y, C, meta, *, batch_size: int, d_true: int,
                           use_pallas: bool = False,
                           block_s: int = 256):
    """``fused_tpe_propose`` vmapped over a leading study axis (the
    StudyBank ask path): X (B, na, dp), y (B, na), C (B, Sp, dp) and one
    packed meta row per study.  The per-study masked ranks come from
    ``meta``, so the whole bank shares one bucketed program regardless of
    how many observations each study holds.  Returns (B, batch_size) pick
    indices."""
    one = functools.partial(fused_tpe_propose, batch_size=batch_size,
                            d_true=d_true, use_pallas=use_pallas,
                            block_s=block_s)
    return jax.vmap(one)(X, y, C, meta)


class TPEStrategy(BaseStrategy):
    needs_gp = True  # needs observations (not an actual GP)

    def __init__(self, dim: int, domain_size: float, gamma: float = 0.25,
                 pending_penalty: bool = False, fit_steps: int = 40,
                 use_pallas: bool = False,
                 refit_every: int = 8):
        # fit_steps/refit_every belong to the standard strategy-constructor
        # contract; TPE has no GP to apply them to, so they are accepted and
        # unused.  Anything else is a typo -> TypeError, like the other
        # strategies.
        if dim < 1:
            raise ValueError(f"TPE needs dim >= 1, got {dim}")
        # gamma is the GOOD quantile; > 0.5 would make the "good" model the
        # majority (nonsensical for TPE) and is what lets the fused program
        # score both splits with one exp per row (disjoint splits)
        if not 0.0 < gamma <= 0.5:
            raise ValueError(f"gamma must be in (0, 0.5], got {gamma}")
        if not domain_size > 0:
            raise ValueError(f"domain_size must be > 0, got {domain_size}")
        self.dim = int(dim)
        self.domain_size = float(domain_size)
        self.gamma = float(gamma)
        self.pending_penalty = bool(pending_penalty)
        self.use_pallas = bool(use_pallas)

    # ------------------------------------------------------------ host oracle
    def _split_count(self, n: int) -> int:
        """Good-split size, computed in float32 like the device program."""
        return max(1, int(np.ceil(np.float32(self.gamma) * np.float32(n))))

    @staticmethod
    def _scott_bw(n_pts: int, d: int) -> np.float32:
        """Scott-rule base bandwidth, computed in float32 like the device."""
        return max(np.float32(max(n_pts, 1)) ** np.float32(-1.0 / (d + 4)),
                   np.float32(1e-2)) * np.float32(0.5) + np.float32(1e-3)

    @staticmethod
    def _dim_scale(pts: np.ndarray) -> np.ndarray:
        """Per-dim bandwidth scale clip(2*std_j, 0.1, 1.0) in f32 — the
        host twin of the device's masked-moment computation."""
        p = np.asarray(pts, np.float32)
        n = np.float32(max(len(p), 1))
        mean = p.sum(axis=0, dtype=np.float32) / n
        var = ((p - mean) ** 2).sum(axis=0, dtype=np.float32) / n
        return np.clip(np.float32(2.0) * np.sqrt(var),
                       np.float32(0.1), np.float32(1.0))

    @staticmethod
    def _kde_sum(pts: np.ndarray, x: np.ndarray, bw) -> np.ndarray:
        """(m, d) per-dim SUM of Gaussian Parzen kernels of x under pts."""
        inv2bw2 = np.float32(0.5) / np.float32(bw * bw)
        d2 = (x[:, None, :] - pts[None, :, :]) ** 2     # (m, n, d)
        return np.exp(-d2 * inv2bw2).sum(axis=1)

    @classmethod
    def _log_kde(cls, pts: np.ndarray, x: np.ndarray) -> np.ndarray:
        """1D-product Parzen log-density of x (m, d) under pts (n, d)."""
        n = max(len(pts), 1)
        dens = cls._kde_sum(pts, x, cls._scott_bw(n, pts.shape[1])) / n
        return np.log(dens + 1e-12).sum(axis=1)

    def propose_host(self, X, y, candidates, batch_size, seed=0,
                     pending=None) -> List[int]:
        """The seed numpy pipeline, kept as the parity oracle for the fused
        device program (same split, per-split bandwidths, tie-breaking).

        Pending rows (when the penalty is on) join the bad mixture at the
        bad split's bandwidth.  In the degenerate empty-bad case — only
        reachable with a single observation, the optimizer never asks with
        fewer than two — the good rows stand in for the bad split at their
        own bandwidth (exactly the device program's per-row-scale
        semantics)."""
        y = np.asarray(y, dtype=float)
        n = len(y)
        d = np.asarray(X).shape[1]
        n_good = self._split_count(n)
        order = np.argsort(-y, kind="stable")  # maximization
        Xa = np.asarray(X)
        good = Xa[order[:n_good]]
        bad = Xa[order[n_good:]]
        pend = (np.asarray(pending, dtype=Xa.dtype)
                if (self.pending_penalty and pending is not None
                    and len(pending)) else Xa[:0])
        ng = len(good)
        nb = (len(bad) if len(bad) else ng) + len(pend)
        bad_eff = bad if len(bad) else good
        b_pts = (np.concatenate([bad_eff, pend]) if len(pend) else bad_eff)
        bw_g = self._scott_bw(ng, d) * self._dim_scale(good)      # (d,)
        bw_b = self._scott_bw(nb, d) * self._dim_scale(b_pts)
        candidates = np.asarray(candidates)
        batch_size = min(batch_size, len(candidates))
        lg = np.log(self._kde_sum(good, candidates, bw_g) / ng
                    + 1e-12).sum(axis=1)
        bad_sum = (self._kde_sum(bad, candidates, bw_b) if len(bad)
                   else self._kde_sum(good, candidates, bw_g))
        if len(pend):
            bad_sum = bad_sum + self._kde_sum(pend, candidates, bw_b)
        lb = np.log(bad_sum / nb + 1e-12).sum(axis=1)
        top = np.argsort(-(lg - lb), kind="stable")[:batch_size]
        return [int(i) for i in top]

    # --------------------------------------------------------- device program
    def propose(self, X, y, candidates, batch_size, seed=0,
                pending=None) -> List[int]:
        X = np.asarray(X, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        C = np.ascontiguousarray(candidates, dtype=np.float32)
        n, d = X.shape
        S = len(C)
        batch_size = min(batch_size, S)
        n_pend = (len(pending)
                  if self.pending_penalty and pending is not None else 0)
        dp = pad_dims(d)
        # pad rows/candidates to stable multiples: a handful of jit cache
        # entries over a whole run, not one per observation count
        na = pad_rows(n + n_pend, 64)
        Sp = pad_rows(S, 256)
        Xb = np.zeros((na, dp), np.float32)
        Xb[:n, :d] = X
        yb = np.zeros(na, np.float32)
        yb[:n] = y
        if n_pend:
            Xb[n:n + n_pend, :d] = np.asarray(pending, dtype=np.float32)
        Cb = np.zeros((Sp, dp), np.float32)
        Cb[:S, :d] = C
        meta = np.array([n, n_pend, S, self.gamma], np.float32)
        picks = fused_tpe_propose(
            Xb, yb, Cb, meta, batch_size=batch_size, d_true=d,
            use_pallas=self.use_pallas)
        picks = jax.device_get(picks)  # one explicit exit sync
        return [int(i) for i in picks]


STRATEGIES["tpe"] = TPEStrategy
