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

The whole proposal is ONE jit'd device program per ask
(``fused_tpe_propose``, vmapped over the bank's studies by
``fused_tpe_propose_bank``): the good/bad split runs as masked ranks over
the padded observation buffer, the O(m n d) product-Parzen scorer is
``tpe_scores``, and the batch is selected with ``lax.top_k`` — only the
(batch_size,) pick indices leave the device.

Pending trials: Hyperopt's parallelism is *naive* — in-flight trials are
ignored, so an async replacement pick degenerates to re-proposing the
current top-b.  ``pending_penalty=True`` (opt-in, off by default to keep
baseline semantics) hallucinates the in-flight configurations into the
*bad*-split KDE ("pessimistic liar"): g(x) rises around pending points, so
replacement picks steer away from duplicating work already in flight.  The
absorb is just one extra membership mask over the same buffer — still one
device program per ask, no matter how many trials are outstanding.

Registered as ``optimizer="tpe"`` so every Tuner feature (schedulers, fault
tolerance, checkpointing) applies to the baseline too.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def pad_dims(d: int) -> int:
    """Lane-pad the encoded dim (>= 8, multiple of 8)."""
    return max(8, int(math.ceil(d / 8)) * 8)


def scott_bandwidth(n_pts, d_true: int):
    """Scott-rule base bandwidth: scalar, count- and dim-dependent only
    (not data-dependent), floored away from zero."""
    n = jnp.maximum(n_pts, 1.0)
    return jnp.maximum(n ** (-1.0 / (d_true + 4)), 1e-2) * 0.5 + 1e-3


_MAX_ELEMS = 4_000_000   # (block, n, 2d) temporary cap (16 MB f32)


def tpe_scores(cands, pts, a, wg, wb, scal, *, d_true: int):
    """l(x)/g(x) log-ratio for every candidate.

    ``a`` (n, dp) is the per-row per-DIM ``1/(2 bw_j^2)`` scale — with
    gamma <= 0.5 every observation belongs to exactly one split, so each
    row carries its own split's bandwidth vector and ONE exp per
    (candidate, row, dim) covers both densities (a two-mask formulation
    pays exactly double).  Per-dim bandwidths (Scott base scaled by each dim's
    split spread) sharpen low-variance dims — categorical one-hot columns
    especially, whose 0/1 support a d-global bandwidth oversmooths.
    ``wg``/``wb`` (n,) are the 0/1 split memberships and ``scal`` packs
    [1/n_g, 1/n_b, 0, 0] as one (1, 4) row.

    Shapes are static at trace time, so the streaming decision is free:
    problems whose (S, n, d) temporary fits ``_MAX_ELEMS`` score in one
    block (no ``lax.map`` per-chunk overhead — it costs real latency at
    small sizes); larger ones stream candidates through the biggest
    256-multiple chunk that both fits the cap and divides S, so the
    temporary stays ~16 MB at any mc_samples.
    """
    S = cands.shape[0]
    n = pts.shape[0]
    Xd = pts[:, :d_true]

    def score_block(cb):
        d2 = (cb[:, None, :d_true] - Xd[None, :, :]) ** 2     # (b, n, d)
        E = jnp.exp(-d2 * a[None, :, :d_true])                # (b, n, d)
        densg = jnp.einsum("snd,n->sd", E, wg) * scal[0, 0] + 1e-12
        densb = jnp.einsum("snd,n->sd", E, wb) * scal[0, 1] + 1e-12
        return jnp.sum(jnp.log(densg) - jnp.log(densb), axis=-1)

    nd = n * d_true
    if S * nd <= _MAX_ELEMS:
        return score_block(cands)
    block = min(S, max(256, _MAX_ELEMS // nd // 256 * 256))
    while block > 256 and S % block:
        block -= 256
    if S % block:
        # a non-256-multiple S: zero-pad up to the block grid (padded rows
        # score garbage, sliced off below) so the temporary cap holds for
        # ANY candidate count
        Sp = -(-S // block) * block
        cands = jnp.pad(cands, ((0, Sp - S), (0, 0)))
    Sp = cands.shape[0]
    out = jax.lax.map(score_block, cands.reshape(Sp // block, block, -1))
    return out.reshape(Sp)[:S]


@functools.partial(jax.jit, static_argnames=("batch_size", "d_true"))
def fused_tpe_propose(X, y, C, meta, *, batch_size: int, d_true: int):
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
    # split count in float32, like the float64 reference's, so ceil ties
    # can't flip against it
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
    score = tpe_scores(C, X, a, wg, wb, scal, d_true=d_true)
    score = jnp.where(jnp.arange(C.shape[0]) < n_cand, score, -jnp.inf)
    _, idx = jax.lax.top_k(score, batch_size)
    return idx


@functools.partial(jax.jit, static_argnames=("batch_size", "d_true"))
def fused_tpe_propose_bank(X, y, C, meta, *, batch_size: int, d_true: int):
    """``fused_tpe_propose`` vmapped over a leading study axis (the
    StudyBank ask path): X (B, na, dp), y (B, na), C (B, Sp, dp) and one
    packed meta row per study.  The per-study masked ranks come from
    ``meta``, so the whole bank shares one bucketed program regardless of
    how many observations each study holds.  Returns (B, batch_size) pick
    indices."""
    one = functools.partial(fused_tpe_propose, batch_size=batch_size,
                            d_true=d_true)
    return jax.vmap(one)(X, y, C, meta)
