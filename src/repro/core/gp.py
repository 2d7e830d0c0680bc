"""JAX Gaussian-process surrogate for batched bandit search.

Design points (vs. the sklearn GP the original Mango wraps):
  * Matern-5/2 ARD kernel, hyperparameters fit by a short jit'd Adam run on
    the log marginal likelihood (the paper uses sklearn defaults; MLE fitting
    is a recorded beyond-paper improvement).
  * fixed-size padded buffers (power-of-two) so the jit cache stays small
    across tuner iterations,
  * O(n^2) rank-1 Cholesky *hallucination* updates for GP-BUCB batch
    selection (Desautels et al. 2014): the posterior mean stays fixed within
    a batch while the variance contracts — the paper's first parallel
    strategy.  The original refits the GP per batch slot (O(n^3) each).
  * ``fused_propose``: the whole GP-BUCB batch loop (posterior -> adaptive-
    beta UCB -> argmax -> rank-1 hallucination) as one jit'd ``lax.fori_loop``
    with zero host transfers inside the loop; only the final pick indices
    leave the device.
  * incremental observation appends (``GaussianProcess.observe``): real
    completions extend the Cholesky in O(n^2) instead of refitting in
    O(fit_steps * n^3); hyperparameters are re-tuned (full refit) only every
    ``refit_every`` new observations.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scoring
from repro.core.scoring import (JITTER, adaptive_beta_dev,  # noqa: F401
                                jitter as _jitter, linv_from_chol,
                                schur_floor as _schur_floor)


# --------------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------------- #
def matern52(x1: jax.Array, x2: jax.Array, ls: jax.Array,
             var: jax.Array) -> jax.Array:
    """x1 (n, d), x2 (m, d), ls (d,) ARD lengthscales -> (n, m)."""
    z1 = x1 / ls
    z2 = x2 / ls
    d2 = (jnp.sum(z1 * z1, -1)[:, None] + jnp.sum(z2 * z2, -1)[None, :]
          - 2.0 * z1 @ z2.T)
    r = jnp.sqrt(jnp.maximum(d2, 1e-12))
    s = jnp.sqrt(5.0) * r
    return var * (1.0 + s + (5.0 / 3.0) * d2) * jnp.exp(-s)


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


# --------------------------------------------------------------------------- #
# Posterior with incremental (hallucination) Cholesky extension
# --------------------------------------------------------------------------- #
@jax.jit
def cholesky_masked(X, mask, ls, var, noise) -> jax.Array:
    return jnp.linalg.cholesky(_masked_kernel(X, mask, ls, var, noise))


@jax.jit
def posterior(X: jax.Array, y: jax.Array, mask: jax.Array, L: jax.Array,
              Xs: jax.Array, ls, var, noise
              ) -> Tuple[jax.Array, jax.Array]:
    """mu/sigma^2 at Xs (m, d) given padded train (n, d) and its Cholesky."""
    Ks = matern52(X, Xs, ls, var) * mask[:, None]        # (n, m)
    alpha = jax.scipy.linalg.cho_solve((L, True), y * mask)
    mu = Ks.T @ alpha
    V = jax.scipy.linalg.solve_triangular(L, Ks, lower=True)  # (n, m)
    var_s = jnp.maximum(var + noise - jnp.sum(V * V, axis=0), 1e-10)
    return mu, var_s


@jax.jit
def chol_append(L: jax.Array, X: jax.Array, mask: jax.Array, idx: jax.Array,
                x_new: jax.Array, ls, var, noise
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-1 extension: write x_new into padded row ``idx`` and extend L.

    Returns (L', X', mask').  O(n^2) instead of a full O(n^3) refit.
    """
    n = X.shape[0]
    X = X.at[idx].set(x_new)
    k_vec = (matern52(X, x_new[None, :], ls, var)[:, 0] * mask)  # (n,)
    l_vec = jax.scipy.linalg.solve_triangular(L, k_vec, lower=True)
    l_vec = jnp.where(jnp.arange(n) < idx, l_vec, 0.0)
    l_nn = jnp.sqrt(jnp.maximum(var + noise + _jitter(var)
                                - jnp.sum(l_vec * l_vec),
                                _schur_floor(var, noise)))
    row = l_vec.at[idx].set(l_nn)
    L = L.at[idx, :].set(row)
    mask = mask.at[idx].set(1.0)
    return L, X, mask


@jax.jit
def kinv_from_chol(L: jax.Array) -> jax.Array:
    """K^{-1} from its Cholesky (identity rows/cols at padded slots).

    Legacy: the live scoring core tracks ``Linv = L^{-1}`` instead
    (``scoring.linv_from_chol``); this survives as the float32-Schur
    baseline for the ``kinv_f32/f64`` benchmark rows and kernel tests.
    """
    return jax.scipy.linalg.cho_solve(
        (L, True), jnp.eye(L.shape[0], dtype=L.dtype))


@jax.jit
def chol_factor_append(L: jax.Array, Linv: jax.Array, X: jax.Array,
                       mask: jax.Array, idx: jax.Array, x_new: jax.Array,
                       ls, var, noise
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array]:
    """``chol_append`` + the rank-1 extension of Linv in one program.

    The track_factor append path: shares the Matern column and the forward
    solve between the L row and the Linv row through the hardened
    ``scoring.factor_append`` (float64 Schur accumulation when x64 is
    enabled, one iterative-refinement step otherwise).
    """
    X = X.at[idx].set(x_new)
    k_vec = matern52(X, x_new[None, :], ls, var)[:, 0] * mask   # (n,)
    L, Linv, _, _ = scoring.factor_append(L, Linv, idx, k_vec, var, noise)
    mask = mask.at[idx].set(1.0)
    return L, Linv, X, mask


def _append_core_uv(L: jax.Array, Kinv: jax.Array, idx: jax.Array,
                    k_vec: jax.Array, var, noise
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Legacy float32 K^{-1} Schur append (L row + block-inverse extension).

    This is the PR-3 path whose conditioning loses picks on near-noiseless
    objectives: the full-matrix rewrite ``Kinv += uuᵀ/schur`` compounds
    float32 error every slot, and downstream scoring pays the cancelling
    ``k(K⁻¹k)`` quadratic form.  Kept (not wired into any strategy) as the
    baseline the ``kinv_f32_schur_*`` benchmark rows measure the hardened
    ``scoring.factor_append`` against.
    """
    n = L.shape[0]
    l_vec = jax.scipy.linalg.solve_triangular(L, k_vec, lower=True)
    u = jax.scipy.linalg.solve_triangular(L, l_vec, trans=1, lower=True)
    schur = jnp.maximum(var + noise + _jitter(var) - k_vec @ u,
                        _schur_floor(var, noise))
    Kinv = _schur_extend(Kinv, u, schur, idx)
    l_vec = jnp.where(jnp.arange(n) < idx, l_vec, 0.0)
    l_nn = jnp.sqrt(jnp.maximum(var + noise + _jitter(var)
                                - jnp.sum(l_vec * l_vec),
                                _schur_floor(var, noise)))
    L = L.at[idx, :].set(l_vec.at[idx].set(l_nn))
    return L, Kinv, u, schur


def _schur_extend(Kinv: jax.Array, u: jax.Array, schur: jax.Array,
                  idx: jax.Array) -> jax.Array:
    """Write the block-inverse extension into row/col ``idx`` of Kinv."""
    Kinv = Kinv + jnp.outer(u, u) / schur
    Kinv = Kinv.at[idx, :].set(-u / schur)
    Kinv = Kinv.at[:, idx].set(-u / schur)
    return Kinv.at[idx, idx].set(1.0 / schur)


# --------------------------------------------------------------------------- #
# Fused device-resident GP-BUCB batch proposal
# --------------------------------------------------------------------------- #
def _fused_pick(X: jax.Array, y: jax.Array, mask: jax.Array, L: jax.Array,
                C: jax.Array, ls, var, noise, n_obs: jax.Array,
                domain_size: jax.Array, batch_size: int) -> jax.Array:
    """GP-BUCB batch selection as one device program (the tentpole hot path).

    One heavy posterior pass (O(n^2 S): cross-covariance + triangular solve)
    runs *once* per batch; a ``lax.fori_loop`` over batch slots then fuses
    adaptive-beta UCB -> argmax -> rank-1 Cholesky hallucination, extending
    the candidate solve ``V = L^{-1} Ks`` by exactly the one new row forward
    substitution would produce — O(n S) per slot instead of the reference's
    per-slot O(n^2 S) recompute.  Nothing crosses the host boundary until
    the final ``(batch_size,)`` pick indices are read out.

    Numerically equivalent to ``HallucinationStrategy``'s Python loop (the
    reference implementation it is tested against): row ``slot`` is the only
    row of V' a from-scratch solve would change, the hallucinated mean
    recomputation is identical, and the standardized UCB surface differs
    from the de-standardized one by a positive affine map — so the argmax,
    and therefore the picks, are identical.
    """
    S = C.shape[0]
    Ks0 = matern52(X, C, ls, var) * mask[:, None]                 # (n, S)
    V0 = jax.scipy.linalg.solve_triangular(L, Ks0, lower=True)
    sig2_0 = jnp.maximum(var + noise - jnp.sum(V0 * V0, axis=0), 1e-10)
    alpha0 = jax.scipy.linalg.cho_solve((L, True), y * mask)
    mu0 = Ks0.T @ alpha0                                          # (S,)

    def pick(b, mu, sig2, avail, picks):
        beta = adaptive_beta_dev(n_obs + b, domain_size)
        acq = mu + jnp.sqrt(beta) * jnp.sqrt(sig2)
        acq = jnp.where(avail, acq, -jnp.inf)
        idx = jnp.argmax(acq).astype(jnp.int32)
        return idx, picks.at[b].set(idx), avail.at[idx].set(False)

    def body(b, carry):
        X, y, mask, L, Ks, V, mu, sig2, avail, picks = carry
        idx, picks, avail = pick(b, mu, sig2, avail, picks)
        slot = (n_obs + b).astype(jnp.int32)
        L, X, mask = chol_append(L, X, mask, slot, C[idx], ls, var, noise)
        # extend the posterior: new cross-covariance row + the one new row
        # of V' = L'^{-1} Ks' (rows < slot are unchanged by construction)
        k_row = matern52(C[idx][None, :], C, ls, var)[0]          # (S,)
        Ks = Ks.at[slot].set(k_row)
        l_row = L[slot]                                           # (n,)
        v_new = (k_row - l_row @ V) / l_row[slot]
        V = V.at[slot].set(v_new)
        sig2 = jnp.maximum(sig2 - v_new * v_new, 1e-10)
        # hallucinate at the posterior mean, then refresh mu the way the
        # reference does (alpha from the extended system)
        y = y.at[slot].set(mu[idx])
        alpha = jax.scipy.linalg.cho_solve((L, True), y * mask)
        mu = Ks.T @ alpha
        return X, y, mask, L, Ks, V, mu, sig2, avail, picks

    # the final slot needs only its pick — the hallucination update after it
    # is unobservable, so loop batch_size-1 times and pick once more outside
    carry = (X.astype(jnp.float32), y.astype(jnp.float32),
             mask.astype(jnp.float32), L, Ks0, V0, mu0, sig2_0,
             jnp.ones((S,), bool), jnp.zeros((batch_size,), jnp.int32))
    carry = jax.lax.fori_loop(0, batch_size - 1, body, carry)
    _, _, _, _, _, _, mu, sig2, avail, picks = carry
    _, picks, _ = pick(jnp.int32(batch_size - 1), mu, sig2, avail, picks)
    return picks


@functools.partial(jax.jit, static_argnames=("batch_size",))
def fused_propose(X: jax.Array, y: jax.Array, mask: jax.Array, L: jax.Array,
                  C: jax.Array, ls, var, noise, n_obs: jax.Array,
                  domain_size: jax.Array, batch_size: int) -> jax.Array:
    """One jit'd device program for the whole GP-BUCB batch (no pending)."""
    return _fused_pick(X, y, mask, L, C, ls, var, noise, n_obs,
                       domain_size, batch_size)


@functools.partial(jax.jit, static_argnames=("batch_size", "pend_cap"))
def fused_propose_pending(X: jax.Array, y: jax.Array, mask: jax.Array,
                          L: jax.Array, P: jax.Array, n_pending: jax.Array,
                          C: jax.Array, ls, var, noise, n_obs: jax.Array,
                          domain_size: jax.Array, batch_size: int,
                          pend_cap: int) -> jax.Array:
    """``fused_propose`` with in-flight trials hallucinated *inside* the
    program (the async replacement-pick hot path).

    A leading ``fori_loop`` over the (padded, ``pend_cap``) pending buffer
    absorbs each in-flight configuration exactly the way the host-side
    ``GaussianProcess.hallucinate`` does — posterior mean at the pending
    point from the current extended system, rank-1 Cholesky append, phantom
    y at the mean — then the standard pick loop runs with the observation
    counter advanced by ``n_pending`` (reproducing the batch-index term of
    the adaptive-beta schedule).  One device dispatch total, vs. the seed's
    one O(n^2) program *per pending trial* per replacement pick.
    """
    def absorb(j, carry):
        def do(c):
            X, y, mask, L = c
            x_new = P[j]
            k_vec = matern52(X, x_new[None, :], ls, var)[:, 0] * mask
            alpha = jax.scipy.linalg.cho_solve((L, True), y * mask)
            mu = k_vec @ alpha
            slot = (n_obs + j).astype(jnp.int32)
            L2, X2, mask2 = chol_append(L, X, mask, slot, x_new,
                                        ls, var, noise)
            return X2, y.at[slot].set(mu), mask2, L2
        return jax.lax.cond(j < n_pending, do, lambda c: c, carry)

    carry = (X.astype(jnp.float32), y.astype(jnp.float32),
             mask.astype(jnp.float32), L)
    X, y, mask, L = jax.lax.fori_loop(0, pend_cap, absorb, carry)
    return _fused_pick(X, y, mask, L, C, ls, var, noise,
                       n_obs + n_pending, domain_size, batch_size)


@functools.partial(jax.jit, static_argnames=("batch_size", "block_s",
                                             "use_pallas"))
def fused_propose_pallas(X: jax.Array, y: jax.Array, mask: jax.Array,
                         L: jax.Array, Linv: jax.Array, C: jax.Array,
                         ls, var, noise, n_obs: jax.Array,
                         domain_size: jax.Array, batch_size: int,
                         block_s: int = 256,
                         use_pallas: bool = True) -> jax.Array:
    """``fused_propose`` on the shared conditioning-hardened scoring core.

    Scoring runs through ``scoring.posterior_scores`` — the
    ``kernels/gp_acquisition`` Pallas kernels when ``use_pallas`` (fused
    Matern + posterior epilogue on the MXU/VPU) or their jnp oracle twin
    otherwise (the "K⁻¹-jit" parity path) — which consumes the triangular
    inverse factor Linv and evaluates variance as a monotone sum of
    squares.  Hallucination extends (L, Linv) via the hardened
    ``scoring.factor_append``; the same (u, schur) pair drives the rank-1
    variance downdate, so per-slot rescoring is O(n S), not O(n^2 S).
    """
    S = C.shape[0]
    Xs, Cs = scoring.prescale(X, C, ls, block_s)
    return scoring.pick_downdate_loop(
        Cs, Xs, S, y.astype(jnp.float32), mask.astype(jnp.float32), L,
        Linv, var, noise, n_obs, domain_size, batch_size,
        use_pallas=use_pallas, block_s=block_s)


@functools.partial(jax.jit, static_argnames=("batch_size", "pend_cap",
                                             "block_s", "use_pallas"))
def fused_propose_pallas_pending(X: jax.Array, y: jax.Array,
                                 mask: jax.Array, L: jax.Array,
                                 Linv: jax.Array, P: jax.Array,
                                 n_pending: jax.Array, C: jax.Array,
                                 ls, var, noise, n_obs: jax.Array,
                                 domain_size: jax.Array, batch_size: int,
                                 pend_cap: int, block_s: int = 256,
                                 use_pallas: bool = True) -> jax.Array:
    """``fused_propose_pallas`` with in-flight trials absorbed *inside* the
    program (the async replacement-pick hot path on the shared core).

    The leading absorb loop is ``scoring.absorb_pending`` — hardened
    factor appends (float64 Schur accumulation / iterative refinement),
    posterior mean at each pending point from the current extended system,
    phantom y at the mean — then the downdate pick loop runs with the
    observation counter advanced by ``n_pending``.  One device dispatch
    total, and the identical absorb loop serves the clustering pipeline
    (``acquisition.fused_cluster_propose``).
    """
    S = C.shape[0]
    Xs, Cs = scoring.prescale(X, C, ls, block_s)
    dp = Xs.shape[1]
    d = X.shape[1]
    Ps = jnp.zeros((pend_cap, dp), jnp.float32).at[:, :d].set(P / ls)
    Xs, y, mask, L, Linv = scoring.absorb_pending(
        Xs, y, mask, L, Linv, Ps, n_pending, n_obs, var, noise, pend_cap)
    return scoring.pick_downdate_loop(
        Cs, Xs, S, y, mask, L, Linv, var, noise, n_obs + n_pending,
        domain_size, batch_size, use_pallas=use_pallas, block_s=block_s)


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
# Staging is bitwise-safe: each stage reproduces the exact op sequence of
# the fused single-study program (division by the lengthscales, the raw-d2
# Matern polynomial, left-associated products, the hardened factor loop),
# and f32 elementwise/dot ops produce identical bits whether or not they
# share a fusion region — verified empirically against ``score_cov_ref``
# and exercised by the bank-vs-single pick-parity suite.
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
    state alone — what makes a resumed bank replay bit-identical — and
    written back so the fleet checkpoint carries ``L``/``L⁻¹``.  ``cond``
    is the power-iteration estimate of cond₂(K) (``scoring.cond_estimate``)
    riding along with the factorization so ``last_cond_proxy`` lands within
    ~2x of the true condition number instead of the 20-50x-low diagonal
    bound."""

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
    """Prescale the fresh candidate block (B, S, d) -> (B, S, dp).

    Unlike the single-study ``scoring.prescale`` there is NO padding of S
    to a Pallas block multiple: the bank pipeline is pure jnp, every
    per-candidate row is independent (distances, posterior moments, and
    downdates are row-local; the argmax never saw padded rows, they were
    masked unavailable), so padded rows were 4x wasted elementwise work at
    small ``mc_samples`` with bitwise-identical picks either way."""
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
    sqrt) — exactly ``kernels.gp_acquisition.ref.matern52``."""

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
            domain_size, batch_size, use_pallas=False)

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
    exploitative pick per cluster — op-for-op the tail of
    ``acquisition.fused_cluster_propose``, vmap'd over the bank.  ``C`` is
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
    (``studybank._y_standardization``) with the exact numpy op sequence of
    the single-study ``GaussianProcess.fit``, and passed in — z is then a
    pure elementwise transform, bit-identical to the host standardization,
    which is what makes the bank-of-one ask path reproduce the pre-refactor
    single-study fits exactly.  Warm-starts from the passed per-study
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


# --------------------------------------------------------------------------- #
# Numpy-facing wrapper
# --------------------------------------------------------------------------- #
def _pad_to(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass
class GPState:
    X: np.ndarray          # (n_pad, d)
    y: np.ndarray          # (n_pad,)
    mask: np.ndarray       # (n_pad,)
    L: Optional[jax.Array]
    ls: jax.Array
    var: jax.Array
    noise: jax.Array
    n: int
    y_mean: float
    y_std: float
    Linv: Optional[jax.Array] = None   # L^{-1}, only when track_factor


def _grow_state(st: GPState) -> GPState:
    """Double the padded buffers; identity rows keep L/Linv consistent."""
    grow = st.X.shape[0]
    pad_idx = jnp.arange(grow, 2 * grow)
    L = jnp.pad(st.L, ((0, grow), (0, grow)))
    L = L.at[pad_idx, pad_idx].set(1.0)
    Linv = st.Linv
    if Linv is not None:
        Linv = jnp.pad(Linv, ((0, grow), (0, grow)))
        Linv = Linv.at[pad_idx, pad_idx].set(1.0)
    return dataclasses.replace(
        st,
        X=np.concatenate([st.X, np.zeros_like(st.X)], 0),
        y=np.concatenate([st.y, np.zeros_like(st.y)], 0),
        mask=np.concatenate([st.mask, np.zeros_like(st.mask)], 0),
        L=L,
        Linv=Linv,
    )


class GaussianProcess:
    """Stateful fit/predict facade used by the batch strategies.

    ``fit`` is the full O(fit_steps * n^3) hyperparameter re-tune; ``observe``
    is the incremental entry point used by the fused proposal path — it
    appends new observations in O(n^2) and falls back to ``fit`` only when
    the observed prefix changed, the data shrank, or ``refit_every`` new
    points accumulated since the last hyperparameter tune.
    """

    def __init__(self, dim: int, fit_steps: int = 40, refit_every: int = 8,
                 track_factor: bool = False,
                 warm_fit_steps: Optional[int] = None):
        self.dim = dim
        self.fit_steps = fit_steps
        # refit boundaries warm-start Adam from the previous log-params and
        # run a short polish instead of the full from-scratch schedule
        self.warm_fit_steps = (max(8, fit_steps // 4)
                               if warm_fit_steps is None else warm_fit_steps)
        self.refit_every = max(1, int(refit_every))
        # maintain Linv = L^{-1} alongside L (the shared scoring core's
        # device-resident operand; was a tracked K^{-1} before ISSUE 5)
        self.track_factor = track_factor
        self.state: Optional[GPState] = None
        self.n_fit = 0                 # obs count at the last full fit
        self._fit_params: Optional[dict] = None  # log-params of the last fit
        self._obs_X: Optional[np.ndarray] = None
        self._obs_y: Optional[np.ndarray] = None

    def fit(self, X: np.ndarray, y: np.ndarray) -> GPState:
        X = np.asarray(X, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        n = X.shape[0]
        n_pad = _pad_to(n)
        y_mean = float(y.mean()) if n else 0.0
        y_std = float(y.std()) + 1e-6 if n else 1.0
        Xp = np.zeros((n_pad, self.dim), np.float32)
        yp = np.zeros((n_pad,), np.float32)
        mp = np.zeros((n_pad,), np.float32)
        Xp[:n] = X
        yp[:n] = (y - y_mean) / y_std
        mp[:n] = 1.0
        steps = self.fit_steps if self._fit_params is None \
            else self.warm_fit_steps
        ls, var, noise, params = fit_hypers(
            jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(mp), steps=steps,
            init=self._fit_params)
        self._fit_params = params
        L = cholesky_masked(jnp.asarray(Xp), jnp.asarray(mp), ls, var, noise)
        Linv = linv_from_chol(L) if self.track_factor else None
        self.state = GPState(Xp, yp, mp, L, ls, var, noise, n, y_mean, y_std,
                             Linv=Linv)
        self.n_fit = n
        self._obs_X, self._obs_y = X, y
        return self.state

    def _append(self, st: GPState, x_new: np.ndarray, y_raw: float
                ) -> GPState:
        """Extend the state with one *real* observation in O(n^2)."""
        if st.n >= st.X.shape[0]:
            st = _grow_state(st)
        idx = jnp.int32(st.n)
        Linv = st.Linv
        if Linv is not None:
            L, Linv, X, mask = chol_factor_append(
                st.L, Linv, jnp.asarray(st.X), jnp.asarray(st.mask), idx,
                jnp.asarray(x_new, jnp.float32), st.ls, st.var, st.noise)
        else:
            L, X, mask = chol_append(st.L, jnp.asarray(st.X),
                                     jnp.asarray(st.mask), idx,
                                     jnp.asarray(x_new, jnp.float32),
                                     st.ls, st.var, st.noise)
        y = st.y.copy()
        y[st.n] = (float(y_raw) - st.y_mean) / st.y_std
        X, mask = jax.device_get((X, mask))  # explicit host-pipeline exit
        return dataclasses.replace(st, X=X, y=y, mask=mask, L=L,
                                   n=st.n + 1, Linv=Linv)

    def observe(self, X: np.ndarray, y: np.ndarray) -> GPState:
        """Incremental fit on the full observation history (X, y)."""
        X = np.asarray(X, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        n = len(y)
        st = self.state
        stale = (
            st is None or n < st.n
            or (n - self.n_fit) >= self.refit_every
            or self._obs_X is None
            or not np.array_equal(self._obs_X[:st.n], X[:st.n])
            or not np.array_equal(self._obs_y[:st.n], y[:st.n]))
        if not stale and n > self.n_fit:
            # frozen standardization sanity: a degenerate fit (y_std ~ 1e-6
            # from constant initial observations) would blow incoming values
            # up to ~1e6 standardized and wreck the acquisition surface for
            # up to refit_every iterations — re-tune immediately instead.
            # Checked over everything appended since the last fit (not just
            # this call's new rows) so a checkpoint-resume replay, whose
            # appends bypass observe(), reaches the same refit decision at
            # the same propose step as the uninterrupted run.
            z = np.abs(y[self.n_fit:n] - st.y_mean) / st.y_std
            stale = bool(z.size) and float(z.max()) > 1e3
        if stale:
            return self.fit(X, y)
        for i in range(st.n, n):
            st = self._append(st, X[i], y[i])
        self.state = st
        self._obs_X, self._obs_y = X, y
        return st

    def restore(self, X: np.ndarray, y: np.ndarray, n_fit: int) -> GPState:
        """Rebuild the exact state an uninterrupted incremental run has:
        full fit on the first ``n_fit`` rows (bit-identical hypers on the
        same device), then replay the rest as O(n^2) appends."""
        X = np.asarray(X, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        n_fit = max(1, min(int(n_fit), len(y)))
        st = self.fit(X[:n_fit], y[:n_fit])
        for i in range(n_fit, len(y)):
            st = self._append(st, X[i], y[i])
        self.state = st
        self._obs_X, self._obs_y = X, y
        return st

    # -------------------------------------------------- exact checkpointing
    def export_state(self) -> Optional[dict]:
        """JSON-able snapshot of the fit schedule: the last full fit's
        observation count and raw log-hyperparameters.  Everything else
        (buffers, Cholesky, standardization) is a pure function of the
        observation history and this pair, so ``restore_exact`` rebuilds the
        live state bit-for-bit without re-running Adam — which matters now
        that fits warm-start from the previous fit in a chain a single
        from-scratch ``restore`` cannot reproduce."""
        if self.state is None or self._fit_params is None:
            return None
        return {"n_fit": int(self.n_fit),
                "log_params": {k: np.asarray(v, np.float32).tolist()
                               for k, v in self._fit_params.items()}}

    def restore_exact(self, X: np.ndarray, y: np.ndarray,
                      snap: dict) -> GPState:
        """Rebuild the exact live state from an ``export_state`` snapshot:
        padded buffers and Cholesky at ``n_fit`` under the stored
        hyperparameters, then replay the remaining rows as O(n^2) appends —
        identical ops to the uninterrupted incremental run."""
        X = np.asarray(X, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        n_fit = max(1, min(int(snap["n_fit"]), len(y)))
        lp = {k: jnp.asarray(np.asarray(v, np.float32))
              for k, v in snap["log_params"].items()}
        self._fit_params = lp
        n_pad = _pad_to(n_fit)
        y_mean = float(y[:n_fit].mean())
        y_std = float(y[:n_fit].std()) + 1e-6
        Xp = np.zeros((n_pad, self.dim), np.float32)
        yp = np.zeros((n_pad,), np.float32)
        mp = np.zeros((n_pad,), np.float32)
        Xp[:n_fit] = X[:n_fit]
        yp[:n_fit] = (y[:n_fit] - y_mean) / y_std
        mp[:n_fit] = 1.0
        ls = jnp.exp(lp["log_ls"])
        var = jnp.exp(lp["log_var"])
        noise = jnp.exp(lp["log_noise"]) + 1e-5
        L = cholesky_masked(jnp.asarray(Xp), jnp.asarray(mp), ls, var, noise)
        Linv = linv_from_chol(L) if self.track_factor else None
        st = GPState(Xp, yp, mp, L, ls, var, noise, n_fit, y_mean, y_std,
                     Linv=Linv)
        self.n_fit = n_fit
        for i in range(n_fit, len(y)):
            st = self._append(st, X[i], y[i])
        self.state = st
        self._obs_X, self._obs_y = X, y
        return st

    def ensure_capacity(self, st: GPState, extra: int) -> GPState:
        """Grow padded buffers until ``extra`` more rows fit (no refit).

        Returns a grown *copy* without persisting it: the stored state only
        grows inside ``_append``, so the buffer-growth schedule is a pure
        function of the observation sequence and checkpoint-resume replay
        (``restore``) reproduces it exactly.
        """
        while st.n + extra > st.X.shape[0]:
            st = _grow_state(st)
        return st

    def predict(self, Xs: np.ndarray, state: Optional[GPState] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
        st = state or self.state
        mu, var_s = posterior(jnp.asarray(st.X), jnp.asarray(st.y),
                              jnp.asarray(st.mask), st.L,
                              jnp.asarray(Xs, dtype=jnp.float32),
                              st.ls, st.var, st.noise)
        mu, var_s = jax.device_get((mu, var_s))  # one explicit exit sync
        mu = mu * st.y_std + st.y_mean
        sd = np.sqrt(var_s) * st.y_std
        return mu, sd

    def hallucinate(self, st: GPState, x_new: np.ndarray) -> GPState:
        """GP-BUCB: extend with a phantom observation at the posterior mean.

        Mean is unchanged (y entry = mu in standardized space); the variance
        contracts through the extended Cholesky.
        """
        if st.n >= st.X.shape[0]:  # grow the padded buffers
            st = _grow_state(st)
        mu_std, _ = posterior(jnp.asarray(st.X), jnp.asarray(st.y),
                              jnp.asarray(st.mask), st.L,
                              jnp.asarray(x_new[None, :], dtype=jnp.float32),
                              st.ls, st.var, st.noise)
        Linv = st.Linv
        if Linv is not None:
            L, Linv, X, mask = chol_factor_append(
                st.L, Linv, jnp.asarray(st.X), jnp.asarray(st.mask),
                jnp.int32(st.n), jnp.asarray(x_new, dtype=jnp.float32),
                st.ls, st.var, st.noise)
        else:
            L, X, mask = chol_append(st.L, jnp.asarray(st.X),
                                     jnp.asarray(st.mask), jnp.int32(st.n),
                                     jnp.asarray(x_new, dtype=jnp.float32),
                                     st.ls, st.var, st.noise)
        y = st.y.copy()
        y[st.n] = float(mu_std[0])
        X, mask = jax.device_get((X, mask))  # explicit host-pipeline exit
        return dataclasses.replace(st, X=X, y=y, mask=mask, L=L,
                                   n=st.n + 1, Linv=Linv)
