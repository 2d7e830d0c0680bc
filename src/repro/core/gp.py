"""JAX Gaussian-process surrogate for batched bandit search, served for a
whole bank of studies at once.

Design points (vs. the sklearn GP the original Mango wraps):
  * Matern-5/2 ARD kernel, hyperparameters fit by a short jit'd Adam run on
    the log marginal likelihood (the paper uses sklearn defaults; MLE fitting
    is a recorded beyond-paper improvement).
  * power-of-two padded buffers, so the jit cache stays small as studies
    grow (``StudyBank``'s bucketing contract).
  * GP-BUCB batch selection (Desautels et al. 2014) by O(n S) rank-1
    variance downdates inside one program: the posterior mean stays fixed
    within a batch while the variance contracts — the paper's first
    parallel strategy.  The original refits the GP per batch slot.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import scoring
from repro.core.scoring import adaptive_beta_dev, jitter as _jitter, matern52


def _masked_kernel(X: jax.Array, mask: jax.Array, ls, var, noise):
    K = matern52(X, X, ls, var)
    m2 = mask[:, None] * mask[None, :]
    K = K * m2
    diag = jnp.where(mask > 0, var + noise + _jitter(var), 1.0)
    return K.at[jnp.diag_indices(X.shape[0])].set(diag)


# --------------------------------------------------------------------------- #
# Marginal-likelihood fit (jit, static buffer)
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("steps",))
def fit_hypers(X: jax.Array, y: jax.Array, mask: jax.Array, steps: int = 40,
               init: Optional[dict] = None,
               ) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Returns (lengthscales (d,), signal var, noise, raw log-params) by Adam
    on -log ML.  ``init`` warm-starts Adam from a previous fit's log-params
    (fresh moments), so refit boundaries pay a short polish run instead of
    re-converging from the default initialization."""
    d = X.shape[1]
    n_eff = jnp.maximum(mask.sum(), 1.0)

    def nll(params):
        ls = jnp.exp(params["log_ls"])
        var = jnp.exp(params["log_var"])
        noise = jnp.exp(params["log_noise"]) + 1e-5
        K = _masked_kernel(X, mask, ls, var, noise)
        L = jnp.linalg.cholesky(K)
        alpha = jax.scipy.linalg.cho_solve((L, True), y * mask)
        ll = (-0.5 * jnp.sum((y * mask) * alpha)
              - jnp.sum(jnp.log(jnp.diagonal(L)) * mask)
              - 0.5 * n_eff * jnp.log(2 * jnp.pi))
        return -ll / n_eff

    if init is None:
        params = {"log_ls": jnp.zeros((d,)) + jnp.log(0.5),
                  "log_var": jnp.zeros(()),
                  "log_noise": jnp.log(jnp.asarray(1e-2))}
    else:
        params = {k: jnp.asarray(v, jnp.float32) for k, v in init.items()}
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    lr, b1, b2 = 0.08, 0.9, 0.999

    def step(carry, i):
        params, m, v = carry
        g = jax.grad(nll)(params)
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        t = i.astype(jnp.float32) + 1
        params = jax.tree.map(
            lambda p, mm, vv: p - lr * (mm / (1 - b1 ** t))
            / (jnp.sqrt(vv / (1 - b2 ** t)) + 1e-8), params, m, v)
        params["log_ls"] = jnp.clip(params["log_ls"], jnp.log(0.01),
                                    jnp.log(10.0))
        return (params, m, v), None

    (params, _, _), _ = jax.lax.scan(step, (params, m, v),
                                     jnp.arange(steps))
    return (jnp.exp(params["log_ls"]), jnp.exp(params["log_var"]),
            jnp.exp(params["log_noise"]) + 1e-5, params)


@jax.jit
def cholesky_masked(X, mask, ls, var, noise) -> jax.Array:
    return jnp.linalg.cholesky(_masked_kernel(X, mask, ls, var, noise))


# --------------------------------------------------------------------------- #
# StudyBank entry points: N studies, one dispatch
# --------------------------------------------------------------------------- #
# The bank ask runs as a STAGED pipeline of small jits rather than one
# monolithic program, for two reasons measured on CPU:
#
#   * XLA:CPU emits a *scalar* ``expf`` per element whenever ``exp`` is
#     fused with any producer (~8x the vectorized cost on a multi-million
#     element Matern block); compiled standalone it vectorizes.
#     ``lax.optimization_barrier`` does not split CPU fusion regions, so
#     the only reliable seam is a jit boundary.  ``bank_exp`` therefore
#     owns the ``exp(-s)`` evaluation and nothing else.
#   * the stages have different invalidation cadences: factors and
#     prescaled observations change only when a study's *observations*
#     change, while candidates are fresh every ask.  Separate entry
#     points let the ledger cache the slow stages (see
#     ``StudyBank._dispatch_gp``) instead of recomputing the Cholesky of
#     every study per ask.
#
# Staging is bitwise-safe: f32 elementwise/dot ops produce identical bits
# whether or not they share a fusion region (the mixed-vs-homogeneous bank
# and golden-picks tests hold the picks bit-equal).
def _f32_matmuls(fn):
    """Trace ``fn`` with float32 matrix products (``"highest"`` precision).

    On the TPU a float32 ``dot`` at default precision runs as one bf16
    pass, about three significant digits.  The GP core cancels in two
    places: squared distances by the expansion ``|c|² + |x|² − 2 c·x``,
    and the variance as ``var + noise − ‖K L⁻ᵀ‖²``.  Measured on a v5e at
    default precision, the hyperparameter fit diverged to non-finite
    values, and with the fit repaired the posterior still carried 4e-2 to
    1.7e-1 of error (standardized units); with float32 products every
    stage agrees with a float64 reference within 3e-4.  On the CPU a
    float32 dot is float32 at any precision, so results are unchanged
    there."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


@jax.jit
@_f32_matmuls
def bank_factors(X: jax.Array, mask: jax.Array, ls, var, noise):
    """Masked-kernel Cholesky factors for every study: (B, na, d) ->
    ``(L, Linv, cond)`` at (B, na, na) / (B,).  Deterministic from ledger
    state alone — what makes a resumed bank replay bit-identical.
    ``cond`` is the power-iteration estimate of cond₂(K)
    (``scoring.cond_estimate``, within ~2x of the true condition number)
    riding along with the factorization; the bank counts the rows past
    ``scoring.COND_PROXY_WARN`` (``factors.ill_conditioned``)."""

    def one(X, mask, ls, var, noise):
        L = cholesky_masked(X, mask, ls, var, noise)
        return L, scoring.linv_from_chol(L), scoring.cond_estimate(L, mask)

    return jax.vmap(one)(X, mask, ls, var, noise)


@jax.jit
def bank_prescale_X(X: jax.Array, ls: jax.Array) -> jax.Array:
    """Lengthscale-divide + lane-pad the observation block (B, na, d) ->
    (B, na, dp); cached with the factors (same invalidation cadence)."""
    d = X.shape[-1]
    dp = max(8, -(-d // 8) * 8)

    def one(X, ls):
        return jnp.zeros((X.shape[0], dp), jnp.float32).at[:, :d].set(
            X / ls)

    return jax.vmap(one)(X, ls)


@jax.jit
def bank_prescale_C(C: jax.Array, ls: jax.Array) -> jax.Array:
    """Prescale the fresh candidate block (B, S, d) -> (B, S, dp).  S is
    not padded: every per-candidate row is independent (distances,
    posterior moments and downdates are row-local)."""
    d = C.shape[-1]
    dp = max(8, -(-d // 8) * 8)

    def one(C, ls):
        return jnp.zeros((C.shape[0], dp), jnp.float32).at[:, :d].set(
            C / ls)

    return jax.vmap(one)(C, ls)


@functools.partial(jax.jit, static_argnames=("pend_cap",))
@_f32_matmuls
def bank_absorb(Xs: jax.Array, y: jax.Array, mask: jax.Array,
                L: jax.Array, Linv: jax.Array, P: jax.Array,
                n_pending: jax.Array, n_obs: jax.Array,
                ls, var, noise, pend_cap: int):
    """Hallucinate each study's in-flight trials into its extended system
    (prescales the raw pending block in-program).  Only dispatched when
    some study has pending trials: with ``n_pending == 0`` the absorb
    loop is an identity, so the no-pending steady state skips the stage
    entirely (bitwise-safely) instead of paying the fori_loop."""
    d = P.shape[-1]
    dp = Xs.shape[-1]

    def one(Xs, y, mask, L, Linv, P, n_pending, n_obs, ls, var, noise):
        Ps = jnp.zeros((pend_cap, dp), jnp.float32).at[:, :d].set(P / ls)
        return scoring.absorb_pending(Xs, y, mask, L, Linv, Ps, n_pending,
                                      n_obs, var, noise, pend_cap)

    return jax.vmap(one)(Xs, y, mask, L, Linv, P, n_pending, n_obs, ls,
                         var, noise)


@jax.jit
@_f32_matmuls
def bank_dist(Cs: jax.Array, Xs: jax.Array):
    """Pairwise squared distances and the Matern argument ``s = sqrt(5) r``
    for every study: (B, Sp, dp) x (B, na, dp) -> (d2, s) at (B, Sp, na).
    The polynomial uses the *raw* d2 (the clamp lives only under the
    sqrt) — exactly ``scoring.matern52``."""

    def one(c, x):
        d2 = (jnp.sum(c * c, -1)[:, None] + jnp.sum(x * x, -1)[None, :]
              - 2.0 * c @ x.T)
        r = jnp.sqrt(jnp.maximum(d2, 1e-12))
        return d2, jnp.sqrt(5.0) * r

    return jax.vmap(one)(Cs, Xs)


@jax.jit
def bank_exp(s: jax.Array) -> jax.Array:
    """``exp(-s)`` and NOTHING else — the one stage that must stay alone
    in its program so XLA:CPU emits the vectorized exponential."""
    return jnp.exp(-s)


@_f32_matmuls
def bank_scores(d2, s, e, y, mask, Linv, var, noise):
    """One study's posterior over its candidates from the staged pieces:
    the masked Matern block ``K`` (S, na), the mean ``K K⁻¹y`` and the
    variance as the conditioning-hardened sum of squares
    ``var + noise − ‖K L⁻ᵀ‖²``.  Returns ``(mu, sig2, K)``; both pick
    heads score through it."""
    K = var * (1.0 + s + (5.0 / 3.0) * d2) * e * mask[None, :]
    alpha = scoring.kinv_matvec(Linv, y * mask)
    mu = K @ alpha
    t = K @ Linv.T
    q = jnp.sum(t * t, axis=-1)
    return mu, jnp.maximum(var + noise - q, 1e-10), K


@functools.partial(jax.jit, static_argnames=("batch_size", "S"))
@_f32_matmuls
def bank_pick(d2: jax.Array, s: jax.Array, e: jax.Array, Cs: jax.Array,
              y: jax.Array, mask: jax.Array, L: jax.Array,
              Linv: jax.Array, var, noise, n_obs_eff: jax.Array,
              domain_size: jax.Array, batch_size: int, S: int):
    """Assemble the masked Matern block from the staged pieces, score
    every candidate through the conditioning-hardened sum-of-squares form,
    and run the GP-BUCB slot loop — one vmap'd dispatch for the bank.
    ``n_obs_eff`` is ``n_obs + n_pending`` (the absorb-advanced counter).
    Returns picked candidate indices (B, batch_size)."""

    def one(d2, s, e, Cs, y, mask, L, Linv, var, noise, n_obs_eff):
        mu, sig2, K = bank_scores(d2, s, e, y, mask, Linv, var, noise)
        return scoring.pick_downdate_from_scores(
            Cs, S, mu, sig2, K, L, Linv, var, noise, n_obs_eff,
            domain_size, batch_size)

    return jax.vmap(one)(d2, s, e, Cs, y, mask, L, Linv, var, noise,
                         n_obs_eff)


@functools.partial(jax.jit, static_argnames=("batch_size", "n_top", "S"))
@_f32_matmuls
def bank_cluster_pick(d2: jax.Array, s: jax.Array, e: jax.Array,
                      C: jax.Array, y: jax.Array, mask: jax.Array,
                      Linv: jax.Array, var, noise, n_obs_eff: jax.Array,
                      domain_size: jax.Array, keys: jax.Array,
                      batch_size: int, n_top: int, S: int):
    """The clustering head on the staged bank pipeline: assemble the masked
    Matern block from the shared ``bank_dist``/``bank_exp`` pieces, score
    every candidate through the hardened sum-of-squares form, then UCB ->
    ``top_k`` -> weighted k-means over the RAW candidate rows -> one
    exploitative pick per cluster, vmap'd over the bank.  ``C`` is
    the *unscaled* candidate block (k-means clusters in raw space);
    ``keys`` carries each study's per-ask PRNG key.  Returns picked
    candidate indices (B, batch_size)."""
    from repro.core.kmeans import _kmeans

    def one(d2, s, e, C, y, mask, Linv, var, noise, n_obs_eff, key):
        mu, sig2, _ = bank_scores(d2, s, e, y, mask, Linv, var, noise)
        beta = adaptive_beta_dev(n_obs_eff, domain_size)
        acq = mu + jnp.sqrt(beta) * jnp.sqrt(sig2)
        acq = jnp.where(jnp.arange(C.shape[0]) < S, acq, -jnp.inf)
        top_vals, top_idx = jax.lax.top_k(acq, n_top)
        w = top_vals - top_vals[n_top - 1] + 1e-6
        assign = _kmeans(C[top_idx], w, key, batch_size)

        def body(c, carry):
            picked, picks = carry
            in_c = (assign == c) & ~picked
            sel = jnp.where(jnp.any(in_c), in_c, ~picked)
            vals = jnp.where(sel, top_vals, -jnp.inf)
            j = jnp.argmax(vals).astype(jnp.int32)
            return picked.at[j].set(True), picks.at[c].set(top_idx[j])

        _, picks = jax.lax.fori_loop(
            0, batch_size, body,
            (jnp.zeros((n_top,), bool),
             jnp.zeros((batch_size,), jnp.int32)))
        return picks

    return jax.vmap(one)(d2, s, e, C, y, mask, Linv, var, noise,
                         n_obs_eff, keys)


@functools.partial(jax.jit, static_argnames=("steps",))
@_f32_matmuls
def fit_hypers_bank(X: jax.Array, y: jax.Array, mask: jax.Array,
                    log_ls: jax.Array, log_var: jax.Array,
                    log_noise: jax.Array, y_mean: jax.Array,
                    y_std: jax.Array, steps: int = 40):
    """``fit_hypers`` for every study in a bank, one dispatch.

    ``y`` is raw signed values at the bucket shape; the frozen
    ``(y_mean, y_std)`` standardization scalars are computed on the HOST
    (``studybank._y_standardization``) and passed in — z is then a pure
    elementwise transform, bit-identical to the host standardization, so a
    resumed study refits exactly.  Warm-starts from the passed per-study
    log-hypers — ledger rows that never fit carry the cold-init values, so
    one fixed-``steps`` program serves cold and warm fits alike (a static
    warm/cold split would double the cache entries per bucket).
    """

    def one(X, y, mask, lls, lv, ln, mean, std):
        z = ((y - mean) / std) * mask
        _, _, _, params = fit_hypers(
            X, z, mask, steps=steps,
            init={"log_ls": lls, "log_var": lv, "log_noise": ln})
        return params["log_ls"], params["log_var"], params["log_noise"]

    return jax.vmap(one)(X, y, mask, log_ls, log_var, log_noise, y_mean,
                         y_std)


# Every jitted bank entry point, by name: the retrace benchmark
# (``benchmarks/multi_study.py``) audits each one's jit cache against the
# number of shape buckets it was dispatched at — one compile per bucket,
# ever, is the shape-bucketing contract.
BANK_JITS = {
    "bank_factors": bank_factors,
    "bank_prescale_X": bank_prescale_X,
    "bank_prescale_C": bank_prescale_C,
    "bank_absorb": bank_absorb,
    "bank_dist": bank_dist,
    "bank_exp": bank_exp,
    "bank_pick": bank_pick,
    "bank_cluster_pick": bank_cluster_pick,
    "fit_hypers_bank": fit_hypers_bank,
}
