"""xLSTM mixers: chunkwise mLSTM (matrix memory) and recurrent sLSTM.

mLSTM follows the stabilized exponential-gating chunkwise form: within a
chunk, gated attention-like matmuls run on the MXU; across chunks a
(B, nh, dk, dv) matrix memory + normalizer + stabilizer are carried through a
``lax.scan`` — O(S) time, O(1) state, which is what makes xlstm-1.3b runnable
at the 524k-token ``long_500k`` shape.  sLSTM is an inherently sequential
scalar-memory recurrence (per the paper) and is lowered as a ``lax.scan``
over time with block-diagonal per-head recurrent weights.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Runtime, dense_init, rmsnorm
from repro.models.mamba import _causal_conv

_CONV_K = 4


# --------------------------------------------------------------------------- #
# mLSTM
# --------------------------------------------------------------------------- #
def mlstm_init(key, cfg: ArchConfig, rt: Runtime) -> dict:
    d, di, nh = cfg.d_model, cfg.lstm_d_inner, cfg.lstm_heads
    dh = di // nh
    ks = jax.random.split(key, 7)
    return {
        "w_up": dense_init(ks[0], d, (d, 2 * di), rt.param_dtype),
        "conv_w": dense_init(ks[1], _CONV_K, (_CONV_K, di), rt.param_dtype),
        "wq": dense_init(ks[2], dh, (nh, dh, dh), rt.param_dtype),
        "wk": dense_init(ks[3], dh, (nh, dh, dh), rt.param_dtype),
        "wv": dense_init(ks[4], dh, (nh, dh, dh), rt.param_dtype),
        "w_gate": dense_init(ks[5], di, (di, 2 * nh), jnp.float32),
        "gate_bias": jnp.concatenate(
            [jnp.zeros((nh,)), jnp.full((nh,), 3.0)]).astype(jnp.float32),
        "out_scale": jnp.ones((di,), rt.param_dtype),
        "w_down": dense_init(ks[6], di, (di, d), rt.param_dtype),
    }


def _mlstm_qkv_gates(p, x, cfg: ArchConfig, rt: Runtime, conv_state=None):
    cd = rt.compute_dtype
    B, S, _ = x.shape
    di, nh = cfg.lstm_d_inner, cfg.lstm_heads
    dh = di // nh
    up = jnp.einsum("bsd,dk->bsk", x.astype(cd), p["w_up"].astype(cd))
    x_m, z = jnp.split(up, 2, axis=-1)
    x_c = jax.nn.silu(_causal_conv(x_m, p["conv_w"], conv_state))
    xh = x_c.reshape(B, S, nh, dh)
    q = jnp.einsum("bsnd,nde->bsne", xh, p["wq"].astype(cd))
    k = jnp.einsum("bsnd,nde->bsne", xh, p["wk"].astype(cd)) * (dh ** -0.5)
    v = jnp.einsum("bsnd,nde->bsne", x_m.reshape(B, S, nh, dh),
                   p["wv"].astype(cd))
    gates = (jnp.einsum("bsi,ig->bsg", x_m.astype(jnp.float32), p["w_gate"])
             + p["gate_bias"])
    logi, logf_pre = jnp.split(gates, 2, axis=-1)       # (B, S, nh)
    logf = -jax.nn.softplus(-logf_pre)                  # log sigmoid
    return q, k, v, logi, logf, z, x_m


def mlstm(p: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime, *,
          batch: int, return_state: bool = False):
    sc, cd = rt.sc, rt.compute_dtype
    B, S, d = x.shape
    di, nh = cfg.lstm_d_inner, cfg.lstm_heads
    dh = di // nh
    q, k, v, logi, logf, z, x_m = _mlstm_qkv_gates(p, x, cfg, rt)

    if rt.pallas and rt.sc.mesh is None and not return_state \
            and S % min(64, S) == 0:
        from repro.kernels.mlstm_chunk.ops import mlstm_mixer
        h = mlstm_mixer(q.swapaxes(1, 2).astype(jnp.float32),
                        k.swapaxes(1, 2).astype(jnp.float32),
                        v.swapaxes(1, 2).astype(jnp.float32),
                        logi.swapaxes(1, 2), logf.swapaxes(1, 2),
                        chunk=min(64, S))
        h = h.swapaxes(1, 2).reshape(B, S, di).astype(cd)
        h = rmsnorm(h, p["out_scale"]) * jax.nn.silu(z)
        return jnp.einsum("bsi,id->bsd", h, p["w_down"].astype(cd))

    L = min(rt.ssm_chunk, S)
    if S % L != 0:
        L = S
    nC = S // L

    def split(t):  # (B, S, ...) -> (nC, B, L, ...)
        return t.reshape(B, nC, L, *t.shape[2:]).swapaxes(0, 1)

    qs, ks_, vs = split(q), split(k), split(v)
    lis, lfs = split(logi), split(logf)

    stash_dt = jnp.bfloat16 if rt.lstm_bf16_states else jnp.float32

    def chunk(carry, inp):
        C_in, n_in, m_in = carry           # (B,nh,dh,dh), (B,nh,dh), (B,nh)
        qc, kc, vc, li, lf = inp
        qf = qc.astype(jnp.float32).swapaxes(1, 2)   # (B, nh, L, dh)
        kf = kc.astype(jnp.float32).swapaxes(1, 2)
        vf = vc.astype(jnp.float32).swapaxes(1, 2)
        lit = li.swapaxes(1, 2)                       # (B, nh, L)
        b = jnp.cumsum(lf.swapaxes(1, 2), axis=-1)    # (B, nh, L) cum log f
        # D[t, s] = b_t - b_s + i_s (s <= t)
        D = b[..., :, None] - b[..., None, :] + lit[..., None, :]
        tri = jnp.tril(jnp.ones((L, L), bool))
        D = jnp.where(tri, D, -jnp.inf)
        m_intra = jnp.max(D, axis=-1)                 # (B, nh, L)
        m_comb = jnp.maximum(m_intra, b + m_in[..., None])
        m_comb = jnp.maximum(m_comb, -1e30)           # guard all -inf rows
        Dn = jnp.exp(D - m_comb[..., None])
        inter_w = jnp.exp(b + m_in[..., None] - m_comb)  # (B, nh, L)
        scores = jnp.einsum("bnld,bnsd->bnls", qf, kf) * Dn
        h_num = (jnp.einsum("bnls,bnsv->bnlv", scores, vf)
                 + inter_w[..., None] * jnp.einsum("bnld,bndv->bnlv", qf, C_in))
        denom = (scores.sum(-1)
                 + inter_w * jnp.einsum("bnld,bnd->bnl", qf, n_in))
        denom = jnp.maximum(jnp.abs(denom), jnp.exp(-m_comb))
        h = h_num / denom[..., None]                  # (B, nh, L, dh)
        # state update to end of chunk
        bL = b[..., -1:]                               # (B, nh, 1)
        dec = bL - b + lit                             # (B, nh, L)
        m_new = jnp.maximum(bL[..., 0] + m_in, jnp.max(dec, axis=-1))
        w_in_state = jnp.exp(bL[..., 0] + m_in - m_new)
        w_tok = jnp.exp(dec - m_new[..., None])        # (B, nh, L)
        C_out = (w_in_state[..., None, None] * C_in
                 + jnp.einsum("bnl,bnld,bnlv->bndv", w_tok, kf, vf))
        n_out = (w_in_state[..., None] * n_in
                 + jnp.einsum("bnl,bnld->bnd", w_tok, kf))
        return (C_out, n_out, m_new), h.swapaxes(1, 2).astype(stash_dt)

    C0 = jnp.zeros((B, nh, dh, dh), jnp.float32)
    n0 = jnp.zeros((B, nh, dh), jnp.float32)
    m0 = jnp.full((B, nh), -1e30, jnp.float32)
    (Cf, nf, mf), hs = jax.lax.scan(chunk, (C0, n0, m0),
                                    (qs, ks_, vs, lis, lfs))
    h = hs.swapaxes(0, 1).reshape(B, S, di).astype(cd)
    h = rmsnorm(h, p["out_scale"])
    h = h * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", h, p["w_down"].astype(cd))
    if return_state:
        state = {"conv": x_m[:, S - (_CONV_K - 1):, :], "C": Cf, "n": nf,
                 "m": mf}
        return out, state
    return out


def mlstm_with_state(p, x, cfg: ArchConfig, rt: Runtime, *, batch: int):
    return mlstm(p, x, cfg, rt, batch=batch, return_state=True)


def mlstm_cache_init(cfg: ArchConfig, rt: Runtime, B: int) -> dict:
    di, nh = cfg.lstm_d_inner, cfg.lstm_heads
    dh = di // nh
    return {
        "conv": jnp.zeros((B, _CONV_K - 1, di), rt.compute_dtype),
        "C": jnp.zeros((B, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((B, nh, dh), jnp.float32),
        "m": jnp.full((B, nh), -1e30, jnp.float32),
    }


def mlstm_decode(p: dict, x: jax.Array, cache: dict, cfg: ArchConfig,
                 rt: Runtime) -> Tuple[jax.Array, dict]:
    cd = rt.compute_dtype
    B = x.shape[0]
    di, nh = cfg.lstm_d_inner, cfg.lstm_heads
    dh = di // nh
    q, k, v, logi, logf, z, x_m = _mlstm_qkv_gates(
        p, x, cfg, rt, conv_state=cache["conv"])
    qf = q[:, 0].astype(jnp.float32)                   # (B, nh, dh)
    kf = k[:, 0].astype(jnp.float32)
    vf = v[:, 0].astype(jnp.float32)
    li, lf = logi[:, 0], logf[:, 0]                    # (B, nh)
    m_new = jnp.maximum(lf + cache["m"], li)
    fp = jnp.exp(lf + cache["m"] - m_new)
    ip = jnp.exp(li - m_new)
    C = fp[..., None, None] * cache["C"] + ip[..., None, None] * (
        kf[..., :, None] * vf[..., None, :])
    n = fp[..., None] * cache["n"] + ip[..., None] * kf
    num = jnp.einsum("bnd,bndv->bnv", qf, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bnd,bnd->bn", qf, n)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(B, 1, di).astype(cd)
    h = rmsnorm(h, p["out_scale"]) * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", h, p["w_down"].astype(cd))
    new_conv = jnp.concatenate([cache["conv"][:, 1:], x_m], axis=1)
    return out, {"conv": new_conv, "C": C, "n": n, "m": m_new}


# --------------------------------------------------------------------------- #
# sLSTM
# --------------------------------------------------------------------------- #
def slstm_init(key, cfg: ArchConfig, rt: Runtime) -> dict:
    d, nh = cfg.d_model, cfg.lstm_heads
    dh = d // nh
    ks = jax.random.split(key, 3)
    bias = jnp.concatenate([
        jnp.zeros((d,)), jnp.zeros((d,)),               # z, i
        jnp.full((d,), 3.0), jnp.zeros((d,))])          # f, o
    return {
        "w_in": dense_init(ks[0], d, (d, 4 * d), rt.param_dtype),
        "r": dense_init(ks[1], dh, (nh, dh, 4 * dh), jnp.float32),
        "bias": bias.astype(jnp.float32),
        "norm_scale": jnp.ones((d,), rt.param_dtype),
        "w_down": dense_init(ks[2], d, (d, d), rt.param_dtype),
    }


def _slstm_cell(p, xt, state, cfg: ArchConfig):
    """xt (B, 4d) pre-computed input projection; state (c, n, h, m) (B, d)."""
    d, nh = cfg.d_model, cfg.lstm_heads
    dh = d // nh
    c, n, h, m = state
    B = xt.shape[0]
    rec = jnp.einsum("bnd,ndk->bnk", h.reshape(B, nh, dh), p["r"])
    # per-head (4dh) blocks are [z|i|f|o] slices: regroup to gate-major (4d)
    rec = rec.reshape(B, nh, 4, dh).transpose(0, 2, 1, 3).reshape(B, 4 * d)
    g = xt.astype(jnp.float32) + rec + p["bias"]
    zt, it, ft, ot = jnp.split(g, 4, axis=-1)
    logf = -jax.nn.softplus(-ft)                        # log sigmoid(f)
    m_new = jnp.maximum(logf + m, it)
    fp = jnp.exp(logf + m - m_new)
    ip = jnp.exp(it - m_new)
    c_new = fp * c + ip * jnp.tanh(zt)
    n_new = fp * n + ip
    h_new = jax.nn.sigmoid(ot) * (c_new / jnp.maximum(n_new, 1e-6))
    return (c_new, n_new, h_new, m_new)


def slstm(p: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime, *,
          batch: int, return_state: bool = False):
    sc, cd = rt.sc, rt.compute_dtype
    B, S, d = x.shape
    xp = jnp.einsum("bsd,dk->bsk", x.astype(cd), p["w_in"].astype(cd))
    stash_dt = jnp.bfloat16 if rt.lstm_bf16_states else jnp.float32

    # Time-chunked scan: the outer lax.scan steps over chunks of U unrolled
    # cell updates.  This amortizes loop overhead AND — critically — lets the
    # backward pass reduce the recurrent-weight gradient once per chunk
    # instead of once per time step (a 64x cut of the dominant all-reduce
    # traffic at train_4k; see EXPERIMENTS.md §Perf xlstm it3).
    U = max(1, min(64, rt.ssm_chunk, S))
    while S % U != 0:
        U //= 2
    nC = S // U

    def chunk_step(state, x_chunk):  # x_chunk (U, B, 4d)
        hs = []
        for t in range(U):
            state = _slstm_cell(p, x_chunk[t], state, cfg)
            hs.append(state[2].astype(stash_dt))
        return state, jnp.stack(hs)

    z = jnp.zeros((B, d), jnp.float32)
    state0 = (z, z, z, jnp.full((B, d), -1e30, jnp.float32))
    xs = xp.swapaxes(0, 1).reshape(nC, U, B, 4 * d)
    (c, n, hf, m), hs = jax.lax.scan(chunk_step, state0, xs)
    h = hs.reshape(S, B, d).swapaxes(0, 1).astype(cd)  # (B, S, d)
    h = rmsnorm(h, p["norm_scale"])
    out = jnp.einsum("bsd,dk->bsk", h, p["w_down"].astype(cd))
    if return_state:
        return out, {"c": c, "n": n, "h": hf, "m": m}
    return out


def slstm_with_state(p, x, cfg: ArchConfig, rt: Runtime, *, batch: int):
    return slstm(p, x, cfg, rt, batch=batch, return_state=True)


def slstm_cache_init(cfg: ArchConfig, rt: Runtime, B: int) -> dict:
    d = cfg.d_model
    z = jnp.zeros((B, d), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((B, d), -1e30, jnp.float32)}


def slstm_decode(p: dict, x: jax.Array, cache: dict, cfg: ArchConfig,
                 rt: Runtime) -> Tuple[jax.Array, dict]:
    cd = rt.compute_dtype
    xp = jnp.einsum("bsd,dk->bsk", x.astype(cd), p["w_in"].astype(cd))
    state = (cache["c"], cache["n"], cache["h"], cache["m"])
    c, n, h, m = _slstm_cell(p, xp[:, 0], state, cfg)
    y = rmsnorm(h[:, None].astype(cd), p["norm_scale"])
    out = jnp.einsum("bsd,dk->bsk", y, p["w_down"].astype(cd))
    return out, {"c": c, "n": n, "h": h, "m": m}
