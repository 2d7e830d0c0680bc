"""Pallas TPU kernel: fused product-Parzen (TPE) l/g log-density scoring.

Tiling mirrors the ``gp_acquisition`` suite: candidates are blocked (BS rows
per grid step) into VMEM; the padded observation buffer, the two split
masks, and the per-row bandwidth scales are small enough to stay resident
for the whole kernel.  Per block and per (static) true dimension j:

    VPU:  d2 = (c_j - x_j)^2                     (BS, n)  one broadcast
          k  = exp(-d2 * a)      a = per-row 1/(2 bw^2) of the row's split
          acc += log(<k, wg>/n_g) - log(<k, wb>/n_b)

The good/bad split is two 0/1 masks plus one scale vector over ONE
observation buffer: with gamma <= 0.5 every row belongs to exactly one
split, so a single exp per (candidate, row, dim) feeds both densities —
the same m*n*d exp count as the numpy host oracle.  The O(m n d)
product-KDE never leaves the chip; only the (S,) score vector does (and in
the fused proposal not even that — ``lax.top_k`` runs on it before
anything transfers).

Padded candidate dims are never touched (``d_true`` is a static closure
argument); padded observation rows carry mask 0 in both splits.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _tpe_score_kernel(c_ref, x_ref, a_ref, wg_ref, wb_ref, scal_ref,
                      out_ref, *, d_true: int):
    """One grid step: score a (BS, dp) block of candidates.

    a_ref is the (n, dp) per-row per-DIM ``1/(2 bw_j^2)`` scale (each row
    carries its split's bandwidth vector; per-dim bandwidths sharpen
    low-variance dims such as categorical one-hots); scal_ref packs
    [1/n_good, 1/n_bad, 0, 0] as a (1, 4) f32 row (the suite's
    SMEM-portable scalar idiom).
    """
    c = c_ref[...]                      # (BS, dp)
    x = x_ref[...]                      # (n, dp)
    a = a_ref[...]                      # (n, dp) per-row per-dim scale
    wg = wg_ref[...]                    # (1, n)  good-split membership
    wb = wb_ref[...]                    # (1, n)  bad-split membership
    inv_ng = scal_ref[0, 0]
    inv_nb = scal_ref[0, 1]

    acc = jnp.zeros((c.shape[0],), jnp.float32)
    for j in range(d_true):             # static: true dims only
        d2 = (c[:, j:j + 1] - x[:, j:j + 1].T) ** 2          # (BS, n)
        k = jnp.exp(-d2 * a[:, j:j + 1].T)   # one exp serves both densities
        densg = jnp.sum(k * wg, axis=-1) * inv_ng + 1e-12    # (BS,)
        densb = jnp.sum(k * wb, axis=-1) * inv_nb + 1e-12
        acc = acc + jnp.log(densg) - jnp.log(densb)
    out_ref[...] = acc[:, None]


@functools.partial(jax.jit,
                   static_argnames=("d_true", "block_s", "interpret"))
def tpe_scores_pallas(cands, pts, a, wg, wb, scal, *, d_true: int,
                      block_s: int = 256, interpret: Optional[bool] = None):
    """cands (S, dp) with S a block multiple; pts (n, dp); a (n, dp)
    per-row per-dim bandwidth scale; wg/wb (n,); scal (1, 4).  Returns
    the (S,) l/g log-ratio scores."""
    S, dp = cands.shape
    n = pts.shape[0]
    grid = (S // block_s,)
    out = pl.pallas_call(
        functools.partial(_tpe_score_kernel, d_true=d_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, dp), lambda i: (i, 0)),   # candidate tile
            pl.BlockSpec((n, dp), lambda i: (0, 0)),         # obs (resident)
            pl.BlockSpec((n, dp), lambda i: (0, 0)),         # per-dim scale
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 1), jnp.float32),
        interpret=interpret_mode(interpret),
    )(cands.astype(jnp.float32), pts.astype(jnp.float32),
      a.astype(jnp.float32), wg[None, :].astype(jnp.float32),
      wb[None, :].astype(jnp.float32), scal.astype(jnp.float32))
    return out[:, 0]


def _parzen_kernel(c_ref, x_ref, w_ref, scal_ref, out_ref, *, d_true: int):
    """Single-density variant: product-Parzen log-density under one masked
    point set (scal packs [inv2bw2, 1/n, 0, 0])."""
    c = c_ref[...]
    x = x_ref[...]
    w = w_ref[...]
    inv2 = scal_ref[0, 0]
    inv_n = scal_ref[0, 1]
    acc = jnp.zeros((c.shape[0],), jnp.float32)
    for j in range(d_true):
        d2 = (c[:, j:j + 1] - x[:, j:j + 1].T) ** 2
        dens = jnp.sum(jnp.exp(-d2 * inv2) * w, axis=-1) * inv_n + 1e-12
        acc = acc + jnp.log(dens)
    out_ref[...] = acc[:, None]


@functools.partial(jax.jit,
                   static_argnames=("d_true", "block_s", "interpret"))
def parzen_logdens_pallas(cands, pts, w, scal, *, d_true: int,
                          block_s: int = 256, interpret: Optional[bool] = None):
    """(S,) product-Parzen log-density of each candidate under (pts, w)."""
    S, dp = cands.shape
    n = pts.shape[0]
    grid = (S // block_s,)
    out = pl.pallas_call(
        functools.partial(_parzen_kernel, d_true=d_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, dp), lambda i: (i, 0)),
            pl.BlockSpec((n, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 1), jnp.float32),
        interpret=interpret_mode(interpret),
    )(cands.astype(jnp.float32), pts.astype(jnp.float32),
      w[None, :].astype(jnp.float32), scal.astype(jnp.float32))
    return out[:, 0]
