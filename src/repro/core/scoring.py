"""Conditioning-hardened device-resident GP posterior-scoring core.

The posterior math behind both GP pick heads (``gp.bank_pick`` and
``gp.bank_cluster_pick``) and the pending-trial absorb (``gp.bank_absorb``):
the Matern kernel, the factor appends, the rank-1 variance downdate and
the GP-BUCB slot loop, each written once.

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
    float32;
  * the Schur complement is computed as ``c − Σl²`` (sum of positives, the
    Cholesky pivot formula) instead of ``c − k·u``, and floors are
    *relative* to the signal scale and shared bit-for-bit with the
    Cholesky path (``scoring.jitter`` / ``scoring.schur_floor``), so a
    binding floor can never split the two paths;
  * ``cond_estimate`` gives a condition-number estimate of K, which the
    bank's factor stage counts against ``COND_PROXY_WARN``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# condition estimate above which float32 posterior scoring is presumed
# unreliable (cond * eps_f32 ~ 1): the bank counts and warns past it, and
# docs point users at raising the noise floor / enabling x64 beyond it
COND_PROXY_WARN = 1e7

JITTER = 1e-6


def jitter(var) -> jax.Array:
    """Diagonal jitter floor, *relative* to the signal variance (1e-6
    absolute or 1e-6·var, whichever is larger).  One definition shared by
    the Cholesky factor (``gp._masked_kernel``) and the hardened factor
    appends — a floor that binds on one path but not the other would
    itself flip near-ties."""
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


def matern52(x1, x2, ls, var):
    """Matern-5/2 ARD kernel: x1 (n, d), x2 (m, d), ls (d,) -> (n, m).
    The polynomial uses the raw squared distance; the clamp lives only
    under the square root."""
    z1 = x1 / ls
    z2 = x2 / ls
    d2 = (jnp.sum(z1 * z1, -1)[:, None] + jnp.sum(z2 * z2, -1)[None, :]
          - 2.0 * z1 @ z2.T)
    r = jnp.sqrt(jnp.maximum(d2, 1e-12))
    s = jnp.sqrt(5.0) * r
    return var * (1.0 + s + (5.0 / 3.0) * d2) * jnp.exp(-s)


def adaptive_beta_dev(t: jax.Array, domain_size: jax.Array) -> jax.Array:
    """Mango's UCB exploration weight, trace-safe: the GP-UCB schedule
    (Srinivas et al.) scaled, as the paper describes, by search-space size
    and completed evaluations, where ``t`` counts the batch slot too
    (GP-BUCB advances t per hallucinated pick):
    ``beta_t = 2 log(domain_size t² π² / (6 δ))``, δ = 0.1, clipped to
    [1, 100]."""
    t = jnp.maximum(t.astype(jnp.float32), 1.0)
    beta = 2.0 * jnp.log(jnp.maximum(domain_size, 2.0) * t * t
                         * (jnp.pi ** 2) / 0.6)
    return jnp.clip(beta, 1.0, 100.0)


@jax.jit
def linv_from_chol(L: jax.Array) -> jax.Array:
    """L⁻¹ (identity rows/cols at padded slots, like L itself)."""
    return jax.scipy.linalg.solve_triangular(
        L, jnp.eye(L.shape[0], dtype=L.dtype), lower=True)


@functools.partial(jax.jit, static_argnames=("iters",))
def cond_estimate(L: jax.Array, mask: jax.Array, iters: int = 16) -> jax.Array:
    """Power-iteration estimate of cond₂(K) from its masked Cholesky factor.

    The diagonal bound ``(max diag L / min diag L)²`` runs 20-50x low on
    correlated kernels; this estimate runs ``iters`` power-iteration
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


# --------------------------------------------------------------------------- #
# Hardened rank-1 factor extension (the fixed Schur append)
# --------------------------------------------------------------------------- #
def factor_append(L: jax.Array, Linv: jax.Array, idx: jax.Array,
                  k_vec: jax.Array, var, noise):
    """Extend (L, Linv) by the point whose masked Matern column is k_vec.

    Returns ``(L', Linv', u, schur)`` where ``u = K⁻¹k`` is the Schur
    vector (feeds the rank-1 variance downdate) and ``schur`` the Schur
    complement.  The new Linv row is ``[-u/l_nn, 1/l_nn]`` — the same
    block-inverse algebra as the old K⁻¹ extension, but written into one
    fresh row instead of rewriting the whole inverse.

    Conditioning: the two triangular solves run as Linv
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
    if not f64:
        l_vec = l_vec + Li @ (kc - Lc @ l_vec)
    u = l_vec @ Li                        # back solve  Lᵀ u = l
    if not f64:
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


def var_downdate(Cs, x_star, Kc, u, schur, sig2, var):
    """Rank-1 variance downdate after absorbing x*.

    With the absorbed point's Schur vector u = K⁻¹k_* and complement
    ``schur``, each candidate's posterior variance contracts by
    ``(k(c, x*) − k_cᵀu)² / schur`` — exactly the extended system's
    block-inverse quadratic form, at O(n) per candidate.  ``Cs`` and
    ``x_star`` are pre-scaled by the lengthscales; returns the new
    variances and the candidates' kernel column against x*.
    """
    knew = matern52(Cs, x_star[None, :], jnp.float32(1.0), var)[:, 0]
    proj = knew - Kc @ u
    return jnp.maximum(sig2 - proj * proj / schur, 1e-10), knew


# --------------------------------------------------------------------------- #
# Shared pending absorption (hardened factor appends, in-program)
# --------------------------------------------------------------------------- #
def absorb_pending(Xs: jax.Array, y: jax.Array, mask: jax.Array,
                   L: jax.Array, Linv: jax.Array, Ps: jax.Array,
                   n_pending: jax.Array, n_obs: jax.Array, var, noise,
                   pend_cap: int):
    """Hallucinate the (padded, ``pend_cap``) pending buffer in-program.

    GP-BUCB semantics: posterior mean at each in-flight point from the
    current extended system, hardened rank-1 (L, Linv) append, phantom y
    at the mean.  Both pick heads absorb through this one loop
    (``gp.bank_absorb``).  ``Ps`` rows are pre-scaled like ``Xs``.
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
# GP-BUCB pick loop (per-slot rank-1 downdates)
# --------------------------------------------------------------------------- #
def pick_downdate_from_scores(Cs: jax.Array, S: int, mu: jax.Array,
                              sig2: jax.Array, Kc: jax.Array, L: jax.Array,
                              Linv: jax.Array, var, noise,
                              n_obs: jax.Array, domain_size: jax.Array,
                              batch_size: int) -> jax.Array:
    """GP-BUCB slot loop over an already-scored candidate set, with O(n S)
    per-slot rescores.

    ``gp.bank_scores`` scores every candidate *and* hands over the masked
    cross-covariance block ``Kc = k(C, X)``.  Hallucinating at the
    posterior mean leaves the mean invariant, so per slot only the
    variance moves: the rank-1 downdate contracts it by
    ``(k(c, x*) − k_cᵀu)²/schur`` from the cached block — O(n S) — instead
    of re-running the O(n² S) quadratic form per slot.  The cached block
    gains the picked point's column each slot, so later downdates see the
    full extended system."""
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
        sig2, k_new = var_downdate(Cs, Cs[idx], Kc, u, schur, sig2, var)
        Kc = Kc.at[:, slot].set(k_new)
        return L, Linv, Kc, sig2, avail, picks

    carry = (L, Linv.astype(jnp.float32), Kc, sig2,
             jnp.arange(Sp) < S, jnp.zeros((batch_size,), jnp.int32))
    carry = jax.lax.fori_loop(0, batch_size - 1, body, carry)
    _, _, _, sig2, avail, picks = carry
    _, picks, _ = pick(jnp.int32(batch_size - 1), sig2, avail, picks)
    return picks
