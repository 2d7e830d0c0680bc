"""Mamba selective-SSM mixer (Jamba's sequence layer).

TPU adaptation: the GPU reference uses a fused warp-parallel scan; here the
recurrence is *chunked* — ``lax.scan`` over sequence chunks with an
associative (Blelloch) scan inside each chunk, so the working set is a
VMEM-sized (B, chunk, d_inner, N) tile instead of the full sequence.  The
Pallas kernel in ``repro/kernels/ssm_scan`` implements the same chunking with
explicit BlockSpecs; this module is the lowering/oracle path.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Runtime, dense_init


def mamba_init(key, cfg: ArchConfig, rt: Runtime) -> dict:
    d, di = cfg.d_model, cfg.ssm_d_inner
    r, N, Kc = cfg.dt_rank, cfg.ssm_state_dim, cfg.ssm_conv_dim
    ks = jax.random.split(key, 5)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "w_in": dense_init(ks[0], d, (d, 2 * di), rt.param_dtype),
        "conv_w": dense_init(ks[1], Kc, (Kc, di), rt.param_dtype),
        "w_x": dense_init(ks[2], di, (di, r + 2 * N), rt.param_dtype),
        "w_dt": dense_init(ks[3], r, (r, di), rt.param_dtype),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),  # softplus^-1(~0.01)
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "w_out": dense_init(ks[4], di, (di, d), rt.param_dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, shift_in=None) -> jax.Array:
    """Depthwise causal conv via Kc shifted adds. x (B, S, di), w (Kc, di)."""
    Kc = w.shape[0]
    B, S, di = x.shape
    if shift_in is None:
        shift_in = jnp.zeros((B, Kc - 1, di), x.dtype)
    xp = jnp.concatenate([shift_in, x], axis=1)  # (B, S+Kc-1, di)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(Kc):
        out = out + xp[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def _ssm_inputs(p, xz, cfg: ArchConfig, rt: Runtime, *, batch: int,
                conv_state=None):
    """Shared pre-scan computation. xz (B, S, 2*di) -> delta, A, Bx terms."""
    sc, cd = rt.sc, rt.compute_dtype
    di, r, N = cfg.ssm_d_inner, cfg.dt_rank, cfg.ssm_state_dim
    bs = sc.div(batch, sc.dp_axes)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c = jax.nn.silu(_causal_conv(x_in, p["conv_w"], conv_state))
    x_c = sc.constrain(x_c, bs, None, sc.div(di, sc.tp_axis))
    xdb = jnp.einsum("bsi,ik->bsk", x_c, p["w_x"].astype(cd))
    dt_r, Bc, Cc = jnp.split(xdb.astype(jnp.float32), [r, r + N], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt_r, p["w_dt"].astype(jnp.float32))
        + p["dt_bias"])
    delta = sc.constrain(delta, bs, None, sc.div(di, sc.tp_axis))
    A = -jnp.exp(p["A_log"])                                   # (di, N)
    Abar = jnp.exp(delta[..., None] * A[None, None])           # (B,S,di,N)
    Bx = (delta * x_c.astype(jnp.float32))[..., None] * Bc[:, :, None, :]
    return x_c, z, Abar, Bx, Cc, x_in


def mamba(p: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime, *,
          batch: int, return_state: bool = False):
    """Full-sequence selective scan. x (B, S, d)."""
    sc, cd = rt.sc, rt.compute_dtype
    B, S, d = x.shape
    di, N = cfg.ssm_d_inner, cfg.ssm_state_dim
    bs = sc.div(batch, sc.dp_axes)

    xz = jnp.einsum("bsd,dk->bsk", x.astype(cd), p["w_in"].astype(cd))
    xz = sc.constrain(xz, bs, None, sc.div(2 * di, sc.tp_axis))
    x_c, z, Abar, Bx, Cc, x_in = _ssm_inputs(p, xz, cfg, rt, batch=batch)

    if rt.pallas and rt.sc.mesh is None and not return_state \
            and S % min(64, S) == 0 and di % min(512, di) == 0:
        from repro.kernels.ssm_scan.ops import selective_scan
        h_dot_c = selective_scan(Abar, Bx, Cc, chunk=min(64, S),
                                 block_d=min(512, di))
        y = h_dot_c + p["D"] * x_c.astype(jnp.float32)
        y = (y.astype(cd) * jax.nn.silu(z))
        return jnp.einsum("bsi,id->bsd", y, p["w_out"].astype(cd))

    Ck = min(rt.ssm_chunk, S)
    if S % Ck != 0:
        Ck = S
    n_chunks = S // Ck

    def chunk_body(h0, inp):
        Abar_c, Bx_c = inp  # (B, Ck, di, N)
        cumA, y = jax.lax.associative_scan(
            lambda a, b: (a[0] * b[0], a[1] * b[0] + b[1]),
            (Abar_c, Bx_c), axis=1)
        h = y + cumA * h0[:, None]
        return h[:, -1], h

    Abar_r = Abar.reshape(B, n_chunks, Ck, di, N).swapaxes(0, 1)
    Bx_r = Bx.reshape(B, n_chunks, Ck, di, N).swapaxes(0, 1)
    h0 = jnp.zeros((B, di, N), jnp.float32)
    h_last, hs = jax.lax.scan(chunk_body, h0, (Abar_r, Bx_r))
    h = hs.swapaxes(0, 1).reshape(B, S, di, N)

    y = jnp.einsum("bsin,bsn->bsi", h, Cc) + p["D"] * x_c.astype(jnp.float32)
    y = (y.astype(cd) * jax.nn.silu(z))
    y = sc.constrain(y, bs, None, sc.div(di, sc.tp_axis))
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"].astype(cd))
    if return_state:
        Kc = cfg.ssm_conv_dim
        state = {"conv": x_in[:, S - (Kc - 1):, :], "h": h_last}
        return out, state
    return out


def mamba_with_state(p, x, cfg: ArchConfig, rt: Runtime, *, batch: int):
    return mamba(p, x, cfg, rt, batch=batch, return_state=True)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def mamba_cache_init(cfg: ArchConfig, rt: Runtime, B: int) -> dict:
    di, N, Kc = cfg.ssm_d_inner, cfg.ssm_state_dim, cfg.ssm_conv_dim
    return {
        "conv": jnp.zeros((B, Kc - 1, di), rt.compute_dtype),
        "h": jnp.zeros((B, di, N), jnp.float32),
    }


def mamba_decode(p: dict, x: jax.Array, cache: dict, cfg: ArchConfig,
                 rt: Runtime) -> Tuple[jax.Array, dict]:
    """One-token step. x (B, 1, d)."""
    cd = rt.compute_dtype
    B = x.shape[0]
    xz = jnp.einsum("bsd,dk->bsk", x.astype(cd), p["w_in"].astype(cd))
    x_c, z, Abar, Bx, Cc, x_in = _ssm_inputs(
        p, xz, cfg, rt, batch=B, conv_state=cache["conv"])
    h = Abar[:, 0] * cache["h"] + Bx[:, 0]              # (B, di, N)
    y = jnp.einsum("bin,bn->bi", h, Cc[:, 0])[:, None]
    y = y + p["D"] * x_c.astype(jnp.float32)
    y = (y.astype(cd) * jax.nn.silu(z))
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"].astype(cd))
    new_conv = jnp.concatenate([cache["conv"][:, 1:], x_in], axis=1)
    return out, {"conv": new_conv, "h": h}
