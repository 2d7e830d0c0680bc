"""Pallas TPU kernel: fused Matern-5/2 + GP posterior scoring.

Tiling: candidates are blocked (BS rows per grid step) into VMEM; the padded
training set (n <= 512 typically), the triangular inverse factor, and alpha
are small enough to live in VMEM for the whole kernel.  Per block:

    MXU:  cross-covariance k (BS, n)  via the |c - x|^2 expansion (one matmul)
          t = k @ L^{-T}              (BS, n)
    VPU:  matern transform, mu/var epilogue (+ rank-1 downdates)

which avoids 3 HBM round-trips of the (S, n) covariance the unfused jnp
version makes (k, t, and the elementwise products each materialize).

The candidate dim d is zero-padded to a lane multiple by ops.py; padded
columns contribute 0 to the distance because both operands are 0 there.

The original fused-UCB kernel (dense K^{-1} quadratic form, beta baked into
the epilogue) was retired with the K^{-1} scoring path: ``score_cov_pallas``
is the one scoring kernel (factor-based, variance as a monotone sum of
squares) and acquisition epilogues live in ``core.scoring``/``core.
acquisition`` on top of its (mu, sig2) output.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _score_cov_kernel(c_ref, x_ref, mask_ref, linvt_ref, alpha_ref, scal_ref,
                      mu_ref, sig2_ref, k_ref):
    """Posterior scoring pass that also *emits* the masked cross-covariance
    block k(C, X) so the batch slot loop can rescore candidates with O(n S)
    rank-1 variance downdates (``_downdate_kernel``) instead of re-running
    the O(n^2 S) quadratic form per slot.

    Conditioning (ISSUE 5): the resident (n, n) operand is the *transposed
    triangular inverse factor* L^{-T}, not K^{-1}, and the posterior
    variance is the monotone sum of squares ``sig2 = var + noise −
    Σ_j (k L^{-T})_j²`` — the Cholesky path's own formula, evaluated as one
    MXU matmul.  The old ``q = Σ (k K^{-1}) · k`` form cancels its large
    mixed-sign intermediates and measured ~250x the float32 error when the
    fitted noise collapses, flipping near-tied argmaxes."""
    c = c_ref[...]                      # (BS, d)  already / lengthscale
    x = x_ref[...]                      # (n, d)   already / lengthscale
    mask = mask_ref[...]                # (1, n)
    var = scal_ref[0, 0]
    noise = scal_ref[0, 1]

    c2 = jnp.sum(c * c, axis=-1, keepdims=True)          # (BS, 1)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True).T        # (1, n)
    d2 = jnp.maximum(c2 + x2 - 2.0 * jax.lax.dot(
        c, x.T, preferred_element_type=jnp.float32), 0.0)
    r = jnp.sqrt(jnp.maximum(d2, 1e-12))
    s = jnp.sqrt(5.0) * r
    k = var * (1.0 + s + (5.0 / 3.0) * d2) * jnp.exp(-s) * mask  # (BS, n)

    t = jax.lax.dot(k, linvt_ref[...],
                    preferred_element_type=jnp.float32)   # (BS, n) = k L^-T
    q = jnp.sum(t * t, axis=-1)
    mu = jnp.sum(k * alpha_ref[...], axis=-1)             # alpha (1, n)
    sig2 = jnp.maximum(var + noise - q, 1e-10)
    mu_ref[...] = mu[:, None]
    sig2_ref[...] = sig2[:, None]
    k_ref[...] = k


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def score_cov_pallas(cands, X, mask, Linv, alpha, var, noise,
                     block_s: int = 256, interpret: Optional[bool] = None):
    """(mu, sig2, cross-covariance block) for cands (S, d) pre-scaled.

    ``Linv`` is the triangular inverse factor L^{-1} (the shared scoring
    core's device-resident operand); the kernel receives its transpose so
    the variance pass is one plain ``dot``.
    """
    S, d = cands.shape
    n = X.shape[0]
    scal = jnp.stack([var, noise, jnp.zeros_like(var),
                      jnp.zeros_like(var)])[None, :]
    grid = (S // block_s,)
    mu, sig2, k = pl.pallas_call(
        _score_cov_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, d), lambda i: (i, 0)),
            pl.BlockSpec((n, d), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, n), lambda i: (0, 0)),         # L^-T (resident)
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_s, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, n), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(cands.astype(jnp.float32), X.astype(jnp.float32),
      mask[None, :].astype(jnp.float32), Linv.T.astype(jnp.float32),
      alpha[None, :].astype(jnp.float32), scal.astype(jnp.float32))
    return mu[:, 0], sig2[:, 0], k


def _downdate_kernel(c_ref, xs_ref, kc_ref, u_ref, sig2_ref, scal_ref,
                     sig2_out_ref, knew_ref):
    """Rank-1 GP-BUCB variance downdate for one absorbed point x*.

    Per candidate c: the posterior variance of the system extended by x*
    contracts by ``(k(c, x*) - k_c^T u)^2 / schur`` where ``u = K^{-1} k_*``
    is the Schur vector of the append and ``k_c`` the *cached* cross-
    covariance row — O(n) per candidate (one matvec against the cached
    block + a fresh (BS,) Matern column) instead of the O(n^2) quadratic
    form a full rescore pays.  Emits the new column k(C, x*) so the caller
    can extend the cached block for the next slot.
    """
    c = c_ref[...]                      # (BS, d)  already / lengthscale
    xs = xs_ref[...]                    # (1, d)   the absorbed point, scaled
    var = scal_ref[0, 0]
    schur = scal_ref[0, 1]

    c2 = jnp.sum(c * c, axis=-1, keepdims=True)          # (BS, 1)
    x2 = jnp.sum(xs * xs, axis=-1, keepdims=True).T      # (1, 1)
    d2 = jnp.maximum(c2 + x2 - 2.0 * jax.lax.dot(
        c, xs.T, preferred_element_type=jnp.float32), 0.0)
    r = jnp.sqrt(jnp.maximum(d2, 1e-12))
    s = jnp.sqrt(5.0) * r
    knew = var * (1.0 + s + (5.0 / 3.0) * d2) * jnp.exp(-s)      # (BS, 1)

    proj = knew - jax.lax.dot(kc_ref[...], u_ref[...].T,
                              preferred_element_type=jnp.float32)  # (BS, 1)
    sig2 = jnp.maximum(sig2_ref[...] - proj * proj / schur, 1e-10)
    sig2_out_ref[...] = sig2
    knew_ref[...] = knew


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def var_downdate_pallas(cands, x_star, Kc, u, schur, sig2, var,
                        block_s: int = 256, interpret: Optional[bool] = None):
    """Apply the rank-1 downdate; returns (sig2', k(C, x*)).

    cands (S, d) and x_star (d,) pre-scaled by lengthscale; Kc (S, n) the
    cached masked cross-covariance block; u (n,) the Schur vector.
    """
    S, d = cands.shape
    n = Kc.shape[1]
    scal = jnp.stack([var, schur, jnp.zeros_like(var),
                      jnp.zeros_like(var)])[None, :]
    grid = (S // block_s,)
    sig2_out, knew = pl.pallas_call(
        _downdate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_s, n), lambda i: (i, 0)),   # cached block
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(cands.astype(jnp.float32), x_star[None, :].astype(jnp.float32),
      Kc.astype(jnp.float32), u[None, :].astype(jnp.float32),
      sig2[:, None].astype(jnp.float32), scal.astype(jnp.float32))
    return sig2_out[:, 0], knew[:, 0]
