"""Conditioning-hardened device-resident GP posterior-scoring core.

The single scoring backend behind every GP strategy (ISSUE 5): the fused
GP-BUCB Pallas path (``gp.fused_propose_pallas[_pending]``) and the fused
clustering pipeline (``acquisition.fused_cluster_propose``) both score
candidates, absorb pending trials, and extend the system through the
functions in this module — one implementation of the posterior math, with
``use_pallas`` only toggling whether the scoring pass executes as the
``kernels/gp_acquisition`` Pallas kernels or as their pure-jnp oracle twin.

Why the old K⁻¹ path flipped picks (the ROADMAP PR-3 follow-up this module
fixes): on near-noiseless objectives the fitted noise collapses, K becomes
ill-conditioned, and the float32 quadratic form ``q = k·(K⁻¹k)`` cancels
catastrophically — its intermediates (``t = k K⁻¹``) are large and
mixed-sign.  Measured on the repro surface, sig2 through the quadratic form
carried ~250x the error of the Cholesky path (6e-4 vs 2.6e-6 on a 1.3e-2
posterior variance — a 5% relative error that flips near-tied argmaxes),
and a same-precision Newton step on K⁻¹ does not help because the
cancellation is in *evaluating* the form, not only in K⁻¹ itself.

Hardening, at the source:

  * the device-resident operand is the *triangular inverse factor*
    ``Linv = L⁻¹`` rather than ``K⁻¹``; posterior variance is the monotone
    sum of squares ``sig2 = var + noise − ‖k_c Linvᵀ‖²`` — still one MXU
    matmul per candidate block, but numerically the Cholesky path's own
    formula (measured 2.2e-6, i.e. parity with the L-based scorer);
  * rank-1 appends extend (L, Linv) by one new row each and never rewrite
    previous rows — the K⁻¹ Schur update (``K⁻¹ += uuᵀ/schur``) rewrote the
    whole matrix every append, compounding error across batch slots;
  * the Schur solves accumulate in float64 when the backend has x64
    enabled, and otherwise apply one step of iterative refinement in
    float32 (``harden=True``, the default);
  * the Schur complement is computed as ``c − Σl²`` (sum of positives, the
    Cholesky pivot formula) instead of ``c − k·u``, and floors are
    *relative* to the signal scale and shared bit-for-bit with the
    Cholesky path (``scoring.jitter`` / ``scoring.schur_floor``), so a
    binding floor can never split the two paths;
  * ``cond_proxy_from_chol`` surfaces a condition-number diagnostic to the
    host (strategies expose it as ``last_cond_proxy``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gp_acquisition.ref import (matern52, score_cov_ref,
                                              var_downdate_ref)

# condition proxy above which float32 posterior scoring is presumed
# unreliable (cond * eps_f32 ~ 1): strategies surface the proxy and docs
# point users at raising the noise floor / enabling x64 beyond it
COND_PROXY_WARN = 1e7

JITTER = 1e-6


def jitter(var) -> jax.Array:
    """Diagonal jitter floor, *relative* to the signal variance (1e-6
    absolute or 1e-6·var, whichever is larger).  One definition shared by
    the Cholesky path (``gp._masked_kernel``/``chol_append``) and the
    hardened factor appends — a floor that binds on one path but not the
    other would itself flip near-ties."""
    return JITTER * jnp.maximum(jnp.asarray(var, jnp.float32), 1.0)


def schur_floor(var, noise) -> jax.Array:
    """Floor for the Schur complement / Cholesky pivot, relative to the
    diagonal scale (keeps 1/schur and 1/l_nn finite when a duplicate point
    is absorbed).  Shared by every append path."""
    return jnp.maximum(jnp.float32(1e-10),
                       1e-8 * (jnp.asarray(var, jnp.float32) + noise))


def compute_dtype():
    """float64 when the backend has x64 enabled (trace-time decision; the
    x64 flag participates in the jit cache key), else float32."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def adaptive_beta_dev(t: jax.Array, domain_size: jax.Array) -> jax.Array:
    """jnp twin of ``acquisition.adaptive_beta`` (delta=0.1), trace-safe."""
    t = jnp.maximum(t.astype(jnp.float32), 1.0)
    beta = 2.0 * jnp.log(jnp.maximum(domain_size, 2.0) * t * t
                         * (jnp.pi ** 2) / 0.6)
    return jnp.clip(beta, 1.0, 100.0)


@jax.jit
def linv_from_chol(L: jax.Array) -> jax.Array:
    """L⁻¹ (identity rows/cols at padded slots, like L itself)."""
    return jax.scipy.linalg.solve_triangular(
        L, jnp.eye(L.shape[0], dtype=L.dtype), lower=True)


@jax.jit
def cond_proxy_from_chol(L: jax.Array, mask: jax.Array) -> jax.Array:
    """Cheap 2-norm condition proxy of K from its Cholesky diagonal:
    ``cond₂(K) >= (max diag L / min diag L)²`` on the active block.  A
    lower bound, not an estimate — but it tracks exactly the collapse mode
    that loses float32 picks (fitted noise → 0 → tiny pivots)."""
    d = jnp.abs(jnp.diagonal(L))
    act = mask > 0
    dmax = jnp.max(jnp.where(act, d, 0.0))
    dmin = jnp.min(jnp.where(act, d, jnp.inf))
    return (dmax / jnp.maximum(dmin, 1e-30)) ** 2


@functools.partial(jax.jit, static_argnames=("iters",))
def cond_estimate(L: jax.Array, mask: jax.Array, iters: int = 16) -> jax.Array:
    """Power-iteration estimate of cond₂(K) from its masked Cholesky factor.

    ``cond_proxy_from_chol`` is a diagonal lower bound that runs 20-50x low
    on correlated kernels; this estimate runs ``iters`` power-iteration
    steps for λmax(K) (via ``K v = L (Lᵀ v)``) and λmax(K⁻¹) (via two
    triangular solves) and multiplies the Rayleigh quotients, which lands
    within ~2x of ``np.linalg.cond`` on the repro surface.  The masked
    region of L is exactly identity (block-diagonal by construction), so
    masking the start vector and every matvec keeps the iteration in the
    active block.  Still cheap enough for the bank factor stage: O(iters·n²)
    per study against the O(n³) Cholesky it rides along with.
    """
    m = (mask > 0).astype(L.dtype)
    v0 = m / jnp.maximum(jnp.sqrt(jnp.sum(m)), 1.0)

    def rayleigh(mv):
        def body(v, _):
            w = mv(v)
            nrm = jnp.sqrt(jnp.sum(w * w))
            return w / jnp.maximum(nrm, 1e-30), None
        v, _ = jax.lax.scan(body, v0, None, length=iters)
        return jnp.sum(v * mv(v))

    def k_mv(v):
        return (L @ ((v * m) @ L)) * m

    def kinv_mv(v):
        t = jax.scipy.linalg.solve_triangular(L, v * m, lower=True)
        t = jax.scipy.linalg.solve_triangular(L, t, lower=True, trans=1)
        return t * m

    return jnp.maximum(rayleigh(k_mv) * rayleigh(kinv_mv), 1.0)


def prescale(X, C, ls, block_s):
    """Zero-pad d to a lane multiple and S to a block multiple, pre-divided
    by the ARD lengthscales (padded columns contribute 0 to distances)."""
    n, d = X.shape
    S = C.shape[0]
    dp = max(8, -(-d // 8) * 8)
    Sp = -(-S // block_s) * block_s
    Xs = jnp.zeros((n, dp), jnp.float32).at[:, :d].set(X / ls)
    Cs = jnp.zeros((Sp, dp), jnp.float32).at[:S, :d].set(C / ls)
    return Xs, Cs


# --------------------------------------------------------------------------- #
# Hardened rank-1 factor extension (the fixed Schur append)
# --------------------------------------------------------------------------- #
def factor_append(L: jax.Array, Linv: jax.Array, idx: jax.Array,
                  k_vec: jax.Array, var, noise, harden: bool = True):
    """Extend (L, Linv) by the point whose masked Matern column is k_vec.

    Returns ``(L', Linv', u, schur)`` where ``u = K⁻¹k`` is the Schur
    vector (feeds the rank-1 variance downdate) and ``schur`` the Schur
    complement.  The new Linv row is ``[-u/l_nn, 1/l_nn]`` — the same
    block-inverse algebra as the old K⁻¹ extension, but written into one
    fresh row instead of rewriting the whole inverse.

    Conditioning (``harden=True``): the two triangular solves run as Linv
    matvecs accumulated in float64 when x64 is enabled; on float32-only
    backends each gets one step of iterative refinement (residual against
    L, corrected through Linv).  The Schur complement uses the Cholesky
    pivot formula ``c − Σl²`` and the shared relative floors.
    """
    n = L.shape[0]
    dt = compute_dtype()
    f64 = dt == jnp.float64
    Lc = L.astype(dt)
    Li = Linv.astype(dt)
    kc = k_vec.astype(dt)
    # transposed products are written vector-first (v @ M == Mᵀ @ v): XLA
    # contracts over M's leading axis in place instead of materializing an
    # (n, n) transpose per op, which dominated the append cost at n=1024
    l_vec = Li @ kc                       # forward solve L l = k
    if harden and not f64:
        l_vec = l_vec + Li @ (kc - Lc @ l_vec)
    u = l_vec @ Li                        # back solve  Lᵀ u = l
    if harden and not f64:
        u = u + (l_vec - u @ Lc) @ Li
    c = (var + noise + jitter(var)).astype(dt)
    active = jnp.arange(n) < idx
    l_vec = jnp.where(active, l_vec, 0.0)
    u = jnp.where(active, u, 0.0)
    schur = jnp.maximum(c - jnp.sum(l_vec * l_vec),
                        schur_floor(var, noise).astype(dt))
    l_nn = jnp.sqrt(schur)
    l32 = l_vec.astype(jnp.float32)
    u32 = u.astype(jnp.float32)
    l_nn32 = l_nn.astype(jnp.float32)
    L = L.at[idx, :].set(l32.at[idx].set(l_nn32))
    Linv = Linv.at[idx, :].set((-u32 / l_nn32).at[idx].set(1.0 / l_nn32))
    return L, Linv, u32, schur.astype(jnp.float32)


def kinv_matvec(Linv: jax.Array, v: jax.Array) -> jax.Array:
    """K⁻¹v through the factor (two triangular matvecs) — alpha etc.
    Vector-first form: no materialized (n, n) transpose."""
    return (Linv @ v) @ Linv


# --------------------------------------------------------------------------- #
# The one scoring entry point (Pallas kernel or jnp twin — same math)
# --------------------------------------------------------------------------- #
def posterior_scores(Cs: jax.Array, Xs: jax.Array, y: jax.Array,
                     mask: jax.Array, Linv: jax.Array, var, noise, *,
                     use_pallas: bool, block_s: int = 256):
    """(mu, sig2, Kc, alpha) for pre-scaled candidates Cs against the
    pre-scaled training set (Xs, mask) through the factor Linv.

    Every GP strategy's device program scores through this function — the
    fused GP-BUCB slot loop and the clustering pipeline alike (the "one
    scoring backend" contract; tests monkeypatch it to verify dispatch).
    """
    from repro.kernels.gp_acquisition.gp_acquisition import score_cov_pallas

    alpha = kinv_matvec(Linv, y * mask)
    if use_pallas:
        mu, sig2, Kc = score_cov_pallas(Cs, Xs, mask, Linv, alpha, var,
                                        noise, block_s=block_s)
    else:
        mu, sig2, Kc = score_cov_ref(Cs, Xs, mask, Linv, alpha,
                                     jnp.float32(1.0), var, noise)
    return mu, sig2, Kc, alpha


def var_downdate(Cs, x_star, Kc, u, schur, sig2, var, *, use_pallas: bool,
                 block_s: int = 256):
    """Rank-1 variance downdate after absorbing x*: kernel or jnp twin."""
    from repro.kernels.gp_acquisition.gp_acquisition import \
        var_downdate_pallas

    if use_pallas:
        return var_downdate_pallas(Cs, x_star, Kc, u, schur, sig2, var,
                                   block_s=block_s)
    return var_downdate_ref(Cs, x_star, Kc, u, schur, sig2,
                            jnp.float32(1.0), var)


# --------------------------------------------------------------------------- #
# Shared pending absorption (hardened factor appends, in-program)
# --------------------------------------------------------------------------- #
def absorb_pending(Xs: jax.Array, y: jax.Array, mask: jax.Array,
                   L: jax.Array, Linv: jax.Array, Ps: jax.Array,
                   n_pending: jax.Array, n_obs: jax.Array, var, noise,
                   pend_cap: int):
    """Hallucinate the (padded, ``pend_cap``) pending buffer in-program.

    GP-BUCB semantics, identical to the host ``GaussianProcess.hallucinate``
    loop: posterior mean at each in-flight point from the current extended
    system, hardened rank-1 (L, Linv) append, phantom y at the mean.  Both
    the fused Pallas proposal and the clustering pipeline absorb through
    this one loop.  ``Ps`` rows are pre-scaled like ``Xs``.
    """
    def absorb(j, carry):
        def do(c):
            Xs, y, mask, L, Linv = c
            x_new = Ps[j]
            k_vec = matern52(Xs, x_new[None, :], jnp.float32(1.0),
                             var)[:, 0] * mask
            mu = k_vec @ kinv_matvec(Linv, y * mask)
            slot = (n_obs + j).astype(jnp.int32)
            L2, Linv2, _, _ = factor_append(L, Linv, slot, k_vec, var,
                                            noise)
            return (Xs.at[slot].set(x_new), y.at[slot].set(mu),
                    mask.at[slot].set(1.0), L2, Linv2)
        return jax.lax.cond(j < n_pending, do, lambda c: c, carry)

    carry = (Xs, y.astype(jnp.float32), mask.astype(jnp.float32), L, Linv)
    return jax.lax.fori_loop(0, pend_cap, absorb, carry)


# --------------------------------------------------------------------------- #
# Shared GP-BUCB pick loop (scoring pass + per-slot rank-1 downdates)
# --------------------------------------------------------------------------- #
def pick_downdate_loop(Cs: jax.Array, Xs: jax.Array, S: int, y: jax.Array,
                       mask: jax.Array, L: jax.Array, Linv: jax.Array,
                       var, noise, n_obs: jax.Array,
                       domain_size: jax.Array, batch_size: int, *,
                       use_pallas: bool, block_s: int = 256) -> jax.Array:
    """GP-BUCB slot loop on the shared scorer with O(n S) per-slot rescores.

    One ``posterior_scores`` pass scores every candidate *and* caches the
    masked cross-covariance block k(C, X).  Hallucinating at the posterior
    mean leaves the mean invariant, so per slot only the variance moves:
    the rank-1 downdate contracts it by ``(k(c, x*) − k_cᵀu)²/schur`` from
    the cached block — O(n S) — instead of re-running the O(n² S)
    quadratic form per slot.  The cached block gains the picked point's
    column each slot, so later downdates see the full extended system.
    """
    # module-attribute call: the "one scoring backend" dispatch test
    # monkeypatches ``scoring.posterior_scores`` and must see this call
    import repro.core.scoring as scoring

    mu, sig2, Kc, _ = scoring.posterior_scores(
        Cs, Xs, y, mask, Linv, var, noise, use_pallas=use_pallas,
        block_s=block_s)
    return pick_downdate_from_scores(
        Cs, S, mu, sig2, Kc, L, Linv, var, noise, n_obs, domain_size,
        batch_size, use_pallas=use_pallas, block_s=block_s)


def pick_downdate_from_scores(Cs: jax.Array, S: int, mu: jax.Array,
                              sig2: jax.Array, Kc: jax.Array, L: jax.Array,
                              Linv: jax.Array, var, noise,
                              n_obs: jax.Array, domain_size: jax.Array,
                              batch_size: int, *, use_pallas: bool,
                              block_s: int = 256) -> jax.Array:
    """The slot loop of ``pick_downdate_loop`` given an already-scored
    candidate set — op-for-op the same program, split out so the staged
    bank pipeline (``gp.bank_pick``) can feed scores whose Matern ``exp``
    was evaluated in its own jit (XLA:CPU scalarizes ``exp`` whenever it
    is fused with any producer; standalone it vectorizes)."""
    import repro.core.scoring as scoring

    Sp = Cs.shape[0]

    def pick(b, sig2, avail, picks):
        beta = adaptive_beta_dev(n_obs + b, domain_size)
        acq = mu + jnp.sqrt(beta) * jnp.sqrt(sig2)
        acq = jnp.where(avail, acq, -jnp.inf)
        idx = jnp.argmax(acq).astype(jnp.int32)
        return idx, picks.at[b].set(idx), avail.at[idx].set(False)

    def body(b, carry):
        L, Linv, Kc, sig2, avail, picks = carry
        idx, picks, avail = pick(b, sig2, avail, picks)
        slot = (n_obs + b).astype(jnp.int32)
        # the cached row IS the masked Matern column of the picked point
        # (columns of not-yet-active slots are zero by construction)
        k_vec = Kc[idx]
        L, Linv, u, schur = factor_append(L, Linv, slot, k_vec, var, noise)
        sig2, k_new = scoring.var_downdate(
            Cs, Cs[idx], Kc, u, schur, sig2, var, use_pallas=use_pallas,
            block_s=block_s)
        Kc = Kc.at[:, slot].set(k_new)
        return L, Linv, Kc, sig2, avail, picks

    carry = (L, Linv.astype(jnp.float32), Kc, sig2,
             jnp.arange(Sp) < S, jnp.zeros((batch_size,), jnp.int32))
    carry = jax.lax.fori_loop(0, batch_size - 1, body, carry)
    _, _, _, sig2, avail, picks = carry
    _, picks, _ = pick(jnp.int32(batch_size - 1), sig2, avail, picks)
    return picks
