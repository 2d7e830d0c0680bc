"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gp_acquisition.gp_acquisition import (score_cov_pallas,
                                                         var_downdate_pallas)
from repro.kernels.gp_acquisition.ops import score_cov
from repro.kernels.gp_acquisition.ref import (matern52, score_cov_ref,
                                              var_downdate_ref)
from repro.kernels.mlstm_chunk.mlstm_chunk import mlstm_chunk
from repro.kernels.mlstm_chunk.ref import mlstm_ref
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.ssm_scan.ssm_scan import ssm_scan
from repro.kernels.tpe_kde.ops import parzen_logdens
from repro.kernels.tpe_kde.ref import tpe_scores_ref
from repro.kernels.tpe_kde.tpe_kde import tpe_scores_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,H,KV,S,hd,causal,dtype", [
    (2, 4, 2, 256, 64, True, jnp.float32),
    (1, 8, 8, 128, 128, True, jnp.float32),
    (2, 6, 2, 256, 64, False, jnp.float32),
    (1, 9, 3, 128, 64, True, jnp.float32),
    (1, 4, 1, 128, 64, True, jnp.bfloat16),   # MQA + bf16
    (2, 2, 2, 64, 32, True, jnp.float32),
])
def test_flash_attention(B, H, KV, S, hd, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("B,S,di,N,bd,ck", [
    (2, 128, 64, 8, 32, 32),
    (1, 64, 128, 16, 64, 16),
    (1, 96, 32, 4, 32, 32),
])
def test_ssm_scan(B, S, di, N, bd, ck):
    ks = jax.random.split(KEY, 3)
    A = jax.random.uniform(ks[0], (B, S, di, N), jnp.float32, 0.5, 0.999)
    Bx = jax.random.normal(ks[1], (B, S, di, N), jnp.float32) * 0.1
    C = jax.random.normal(ks[2], (B, S, N), jnp.float32)
    out = ssm_scan(A, Bx, C, block_d=bd, chunk=ck)
    ref = ssm_scan_ref(A, Bx, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("B,NH,S,dh,L", [
    (2, 2, 128, 64, 32),
    (1, 4, 64, 128, 16),
    (1, 1, 64, 32, 64),   # single chunk == whole sequence
])
def test_mlstm_chunk(B, NH, S, dh, L):
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, NH, S, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, NH, S, dh), jnp.float32) * (dh ** -0.5)
    v = jax.random.normal(ks[2], (B, NH, S, dh), jnp.float32)
    li = jax.random.normal(ks[3], (B, NH, S), jnp.float32)
    lf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, NH, S)) - 1.0)
    out = mlstm_chunk(q, k, v, li, lf, chunk=L)
    ref = mlstm_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)


@pytest.mark.parametrize("n,d,S", [(64, 5, 500), (32, 3, 300), (128, 11, 257)])
def test_gp_acquisition(n, d, S):
    """The public scoring wrapper (``ops.score_cov``: S padded to a block
    multiple, d to a lane multiple) matches the unpadded factor oracle."""
    import scipy.linalg as sla

    rng = np.random.default_rng(0)
    X = rng.uniform(size=(n, d)).astype(np.float32)
    mask = np.ones(n, np.float32)
    mask[n - n // 4:] = 0.0
    ls = np.full(d, 0.5, np.float32)
    var, noise = 1.3, 0.01
    K = np.asarray(matern52(jnp.asarray(X / ls), jnp.asarray(X / ls),
                            1.0, var))
    K = K * mask[:, None] * mask[None, :]
    K[np.diag_indices(n)] = np.where(mask > 0, var + noise + 1e-6, 1.0)
    L = np.linalg.cholesky(K)
    Linv = sla.solve_triangular(L, np.eye(n), lower=True).astype(np.float32)
    y = (rng.normal(size=n) * mask).astype(np.float32)
    alpha = (Linv.T @ (Linv @ y)).astype(np.float32)
    C = rng.uniform(size=(S, d)).astype(np.float32)
    mu, sig2 = score_cov(C, X, mask, Linv, alpha, ls, var, noise)
    ref_mu, ref_sig2, _ = score_cov_ref(
        jnp.asarray(C / ls), jnp.asarray(X / ls), jnp.asarray(mask),
        jnp.asarray(Linv), jnp.asarray(alpha), 1.0, var, noise)
    np.testing.assert_allclose(mu, np.asarray(ref_mu), atol=1e-4)
    np.testing.assert_allclose(sig2, np.asarray(ref_sig2), atol=1e-4)


def _gp_system(n=64, d=5, S=512, seed=0):
    import scipy.linalg as sla

    rng = np.random.default_rng(seed)
    X = rng.uniform(size=(n, d)).astype(np.float32)
    mask = np.ones(n, np.float32)
    mask[n - n // 4:] = 0.0
    ls = np.full(d, 0.5, np.float32)
    var, noise = 1.3, 0.01
    K = np.asarray(matern52(jnp.asarray(X / ls), jnp.asarray(X / ls),
                            1.0, var))
    K = K * mask[:, None] * mask[None, :]
    K[np.diag_indices(n)] = np.where(mask > 0, var + noise + 1e-6, 1.0)
    # the scoring kernel consumes the triangular inverse factor L^{-1}
    # (ISSUE 5); K and K^{-1} stay around for the from-scratch checks
    L = np.linalg.cholesky(K).astype(np.float32)
    Linv = sla.solve_triangular(L, np.eye(n, dtype=np.float32),
                                lower=True).astype(np.float32)
    y = (rng.normal(size=n) * mask).astype(np.float32)
    C = rng.uniform(size=(S, d)).astype(np.float32)
    # pre-scaled, lane-padded coords (what the fused proposal feeds in)
    dp = 8
    Cs = np.zeros((S, dp), np.float32)
    Cs[:, :d] = C / ls
    Xs = np.zeros((n, dp), np.float32)
    Xs[:, :d] = X / ls
    return Xs, Cs, mask, K, Linv, y, var, noise


def test_gp_score_cov_kernel():
    """score+cross-covariance kernel vs the jnp oracle (mu, sig2, block)."""
    Xs, Cs, mask, _, Linv, y, var, noise = _gp_system()
    alpha = Linv.T @ (Linv @ y)
    mu, sig2, Kc = score_cov_pallas(
        jnp.asarray(Cs), jnp.asarray(Xs), jnp.asarray(mask),
        jnp.asarray(Linv), jnp.asarray(alpha), jnp.float32(var),
        jnp.float32(noise))
    mu_r, sig2_r, Kc_r = score_cov_ref(
        jnp.asarray(Cs), jnp.asarray(Xs), jnp.asarray(mask),
        jnp.asarray(Linv), jnp.asarray(alpha), 1.0, var, noise)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sig2), np.asarray(sig2_r),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(Kc), np.asarray(Kc_r), atol=1e-5)


def test_gp_score_cov_sumsq_matches_direct_posterior():
    """The factor sum-of-squares variance equals the from-scratch posterior
    ``var + noise − k K^{-1} kᵀ`` computed in float64 — the conditioning
    contract of the hardened scorer."""
    Xs, Cs, mask, K, Linv, y, var, noise = _gp_system()
    alpha = Linv.T @ (Linv @ y)
    mu, sig2, Kc = score_cov_pallas(
        jnp.asarray(Cs), jnp.asarray(Xs), jnp.asarray(mask),
        jnp.asarray(Linv), jnp.asarray(alpha), jnp.float32(var),
        jnp.float32(noise))
    kC = np.asarray(Kc, np.float64)
    q = np.sum((kC @ np.linalg.inv(K.astype(np.float64))) * kC, -1)
    sig2_direct = np.maximum(var + noise - q, 1e-10)
    np.testing.assert_allclose(np.asarray(sig2), sig2_direct, atol=2e-5)
    mu_direct = kC @ np.linalg.solve(K.astype(np.float64),
                                     y.astype(np.float64))
    np.testing.assert_allclose(np.asarray(mu), mu_direct, atol=2e-4)


def test_gp_var_downdate_kernel_matches_extended_system():
    """The rank-1 downdate kernel equals (a) the jnp oracle and (b) the
    from-scratch variance of the system extended by the absorbed point."""
    Xs, Cs, mask, K, Linv, y, var, noise = _gp_system()
    alpha = Linv.T @ (Linv @ y)
    _, sig2, Kc = score_cov_pallas(
        jnp.asarray(Cs), jnp.asarray(Xs), jnp.asarray(mask),
        jnp.asarray(Linv), jnp.asarray(alpha), jnp.float32(var),
        jnp.float32(noise))
    star = 17                        # absorb candidate 17
    x_star = Cs[star]
    k_star = np.asarray(Kc)[star]    # masked cross-covariance row
    u = np.linalg.solve(K, k_star).astype(np.float32)
    schur = float(var + noise + 1e-6 - k_star @ u)
    sig2_dd, k_new = var_downdate_pallas(
        jnp.asarray(Cs), jnp.asarray(x_star), Kc, jnp.asarray(u),
        jnp.float32(schur), sig2, jnp.float32(var))
    sig2_ref, k_new_ref = var_downdate_ref(
        jnp.asarray(Cs), jnp.asarray(x_star), Kc, jnp.asarray(u),
        schur, sig2, 1.0, var)
    np.testing.assert_allclose(np.asarray(sig2_dd), np.asarray(sig2_ref),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(k_new), np.asarray(k_new_ref),
                               atol=1e-5)
    # downdates can only shrink the variance
    assert np.all(np.asarray(sig2_dd) <= np.asarray(sig2) + 1e-7)
    # (b) from-scratch: append x* to K and recompute candidate variances —
    # the downdate is the extended system's exact variance, not an
    # approximation
    n = Xs.shape[0]
    K_ext = np.zeros((n + 1, n + 1), np.float32)
    K_ext[:n, :n] = K
    K_ext[:n, n] = K_ext[n, :n] = k_star
    K_ext[n, n] = var + noise + 1e-6
    kC_ext = np.concatenate([np.asarray(Kc),
                             np.asarray(k_new)[:, None]], 1)     # (S, n+1)
    t = kC_ext @ np.linalg.inv(K_ext)
    sig2_scratch = np.maximum(var + noise - np.sum(t * kC_ext, -1), 1e-10)
    np.testing.assert_allclose(np.asarray(sig2_dd), sig2_scratch, atol=2e-3)


@pytest.mark.parametrize("m,n,d", [(500, 20, 2), (300, 64, 5), (257, 33, 11)])
def test_tpe_parzen_logdens_matches_host_oracle(m, n, d):
    """The padded Pallas product-Parzen log-density == TPEStrategy's numpy
    ``_log_kde`` (same Scott bandwidth, same eps floor)."""
    from repro.core.tpe import TPEStrategy

    rng = np.random.default_rng(0)
    pts = rng.uniform(size=(n, d)).astype(np.float32)
    cands = rng.uniform(size=(m, d)).astype(np.float32)
    out = parzen_logdens(cands, pts)
    host = TPEStrategy._log_kde(pts, cands)
    np.testing.assert_allclose(out, host, atol=1e-4)


@pytest.mark.parametrize("S,n,d_true", [(512, 64, 4), (256, 24, 8)])
def test_tpe_score_kernel_matches_ref(S, n, d_true):
    """Fused two-split score kernel == the pure-jnp oracle on padded
    buffers with masked-out rows in both splits."""
    rng = np.random.default_rng(3)
    dp = 8 if d_true <= 8 else 16
    C = np.zeros((S, dp), np.float32)
    C[:, :d_true] = rng.uniform(size=(S, d_true))
    X = np.zeros((n, dp), np.float32)
    X[: n - 4, :d_true] = rng.uniform(size=(n - 4, d_true))  # 4 padded rows
    wg = np.zeros(n, np.float32)
    wb = np.zeros(n, np.float32)
    wg[: (n - 4) // 4] = 1.0
    wb[(n - 4) // 4: n - 4] = 1.0
    # per-row per-DIM scale: distinct values along dims so a kernel that
    # flattened the dim axis would fail parity
    a = np.where(wg[:, None] > 0, np.float32(3.1), np.float32(5.7)) \
        * np.linspace(0.5, 1.5, dp, dtype=np.float32)[None, :]
    scal = np.array([[1.0 / wg.sum(), 1.0 / wb.sum(), 0.0, 0.0]],
                    np.float32)
    out = tpe_scores_pallas(jnp.asarray(C), jnp.asarray(X),
                            jnp.asarray(a), jnp.asarray(wg),
                            jnp.asarray(wb), jnp.asarray(scal),
                            d_true=d_true, block_s=256)
    ref = tpe_scores_ref(jnp.asarray(C), jnp.asarray(X),
                         jnp.asarray(a), jnp.asarray(wg),
                         jnp.asarray(wb), jnp.asarray(scal), d_true=d_true)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# --------------------------------------------------------------------------- #
# interpret mode follows the backend
# --------------------------------------------------------------------------- #
def test_interpret_mode_is_on_exactly_on_the_cpu(monkeypatch):
    from repro.kernels import interpret_mode
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert interpret_mode(True) is True
    assert interpret_mode(False) is False   # compile for a described TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    with pytest.raises(ValueError, match="CPU backend"):
        interpret_mode(True)
