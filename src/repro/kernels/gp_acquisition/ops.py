"""jit'd wrapper: pads shapes to kernel-friendly sizes and dispatches.

``gp_mean_std`` adapts a ``repro.core.gp.GPState`` to the fused kernel so the
batch strategies can use it via ``Tuner(config={"use_pallas": True})``.
The kernel is compiled on a TPU and interpreted on the CPU
(``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gp_acquisition.gp_acquisition import score_cov_pallas


def _pad_rows(a: np.ndarray, m: int) -> np.ndarray:
    return np.pad(a, [(0, m - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _prescale(cands, X, ls, block_s):
    cands = np.asarray(cands, np.float32)
    S, d = cands.shape
    dp = max(8, int(math.ceil(d / 8)) * 8)
    Sp = int(math.ceil(S / block_s)) * block_s
    ls = np.broadcast_to(np.asarray(ls, np.float32), (d,))
    c = np.zeros((Sp, dp), np.float32)
    c[:S, :d] = cands / ls
    Xp = np.zeros((X.shape[0], dp), np.float32)
    Xp[:, :d] = np.asarray(X, np.float32) / ls
    return c, Xp, S


def score_cov(cands, X, mask, Linv, alpha, ls, var, noise, *,
              block_s: int = 256):
    """(mu, sig2) for every candidate in ONE kernel dispatch (the cached
    cross-covariance block the kernel also emits is dropped here).
    ``Linv`` is the triangular inverse factor L^{-1}."""
    c, Xp, S = _prescale(cands, X, ls, block_s)
    mu, sig2, _ = score_cov_pallas(
        jnp.asarray(c), jnp.asarray(Xp), jnp.asarray(mask, jnp.float32),
        jnp.asarray(Linv, jnp.float32), jnp.asarray(alpha, jnp.float32),
        jnp.asarray(var, jnp.float32), jnp.asarray(noise, jnp.float32),
        block_s=block_s)
    mu, sig2 = jax.device_get((mu, sig2))  # one explicit adapter exit
    return mu[:S], sig2[:S]


def gp_mean_std(st, cands):
    """GPState-facing adapter returning (mu, sd) in the original y scale."""
    if getattr(st, "Linv", None) is not None:
        # incrementally-maintained factor (track_factor): no O(n^3) solve
        Linv = np.asarray(st.Linv)
    else:
        L = np.asarray(st.L)
        eye = np.eye(L.shape[0], dtype=np.float32)
        import scipy.linalg as sla
        Linv = sla.solve_triangular(L, eye, lower=True)
    alpha = Linv.T @ (Linv @ (np.asarray(st.y, np.float32)
                              * np.asarray(st.mask, np.float32)))
    var = float(st.var)
    noise = float(st.noise)
    # one scoring-kernel dispatch yields both moments (the old path ran
    # the UCB kernel twice, with beta=0 and beta=1, to recover sd)
    mu, sig2 = score_cov(cands, st.X, st.mask, Linv, alpha,
                         np.asarray(st.ls), var, noise)
    return mu * st.y_std + st.y_mean, np.sqrt(sig2) * st.y_std
