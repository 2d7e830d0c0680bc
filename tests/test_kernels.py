"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scoring import matern52, var_downdate
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm_chunk.mlstm_chunk import mlstm_chunk
from repro.kernels.mlstm_chunk.ref import mlstm_ref
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.ssm_scan.ssm_scan import ssm_scan

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
    # the scoring core consumes the triangular inverse factor L^{-1};
    # K stays around for the from-scratch checks
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


def test_gp_var_downdate_kernel_matches_extended_system():
    """The rank-1 variance downdate the GP-BUCB slot loop runs
    (``scoring.var_downdate``) equals the from-scratch variance of the
    system extended by the absorbed point, and returns the candidates'
    kernel column against it."""
    Xs, Cs, mask, K, Linv, y, var, noise = _gp_system()
    Kc = matern52(jnp.asarray(Cs), jnp.asarray(Xs), 1.0,
                  var) * mask[None, :]                      # (S, n)
    t = np.asarray(Kc) @ Linv.T
    sig2 = jnp.asarray(np.maximum(var + noise - np.sum(t * t, -1), 1e-10),
                       jnp.float32)
    star = 17                        # absorb candidate 17
    x_star = Cs[star]
    k_star = np.asarray(Kc)[star]    # masked cross-covariance row
    u = np.linalg.solve(K, k_star).astype(np.float32)
    schur = float(var + noise + 1e-6 - k_star @ u)
    sig2_dd, k_new = var_downdate(
        jnp.asarray(Cs), jnp.asarray(x_star), Kc, jnp.asarray(u),
        jnp.float32(schur), sig2, jnp.float32(var))
    k_direct = matern52(jnp.asarray(Cs), jnp.asarray(x_star)[None, :],
                        1.0, var)[:, 0]
    np.testing.assert_allclose(np.asarray(k_new), np.asarray(k_direct),
                               atol=1e-5)
    # downdates can only shrink the variance
    assert np.all(np.asarray(sig2_dd) <= np.asarray(sig2) + 1e-7)
    # from scratch: append x* to K and recompute candidate variances —
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
