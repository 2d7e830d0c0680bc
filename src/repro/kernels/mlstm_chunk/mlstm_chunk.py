"""Pallas TPU kernel: chunkwise stabilized mLSTM (xLSTM matrix memory).

grid = (B*NH, S/L) with the chunk dimension sequential; VMEM scratch carries
the (dh, dh) matrix memory C, the (dh,) normalizer n, and the (1,) stabilizer
m across chunks.  Within a chunk the intra-chunk part is two MXU matmuls
((L, dh) x (dh, L) scores and (L, L) x (L, dh) values) plus a VPU decay-matrix
epilogue — the standard chunkwise-parallel linear-attention decomposition,
with the xLSTM max-stabilizer threaded through exactly as in the recurrent
form so exp() never overflows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, out_ref,
                  C_ref, n_ref, m_ref, *, L: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    q = q_ref[0].astype(jnp.float32)          # (L, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li = li_ref[0, :, 0]                       # (L,)
    lf = lf_ref[0, :, 0]
    m_in = m_ref[0, 0]

    b = jnp.cumsum(lf)                         # (L,) inclusive cum log f
    # D[t, s] = b_t - b_s + i_s for s <= t
    D = b[:, None] - b[None, :] + li[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    D = jnp.where(tri, D, NEG)
    m_intra = jnp.max(D, axis=-1)              # (L,)
    m_comb = jnp.maximum(jnp.maximum(m_intra, b + m_in), NEG)
    Dn = jnp.exp(D - m_comb[:, None])
    inter_w = jnp.exp(b + m_in - m_comb)       # (L,)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * Dn
    h_num = (jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
             + inter_w[:, None] * jax.lax.dot(
                 q, C_ref[...], preferred_element_type=jnp.float32))
    denom = (jnp.sum(scores, axis=-1)
             + inter_w * jnp.sum(q * n_ref[0:1, :], axis=-1))
    denom = jnp.maximum(jnp.abs(denom), jnp.exp(-m_comb))
    out_ref[0] = (h_num / denom[:, None]).astype(out_ref.dtype)

    # ---- state update to end of chunk ----
    bL = b[L - 1]
    dec = bL - b + li                          # (L,)
    m_new = jnp.maximum(bL + m_in, jnp.max(dec))
    w_state = jnp.exp(bL + m_in - m_new)
    w_tok = jnp.exp(dec - m_new)               # (L,)
    kw = k * w_tok[:, None]                    # (L, dh)
    C_ref[...] = (w_state * C_ref[...]
                  + jax.lax.dot_general(kw, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    n_ref[...] = w_state * n_ref[...] + jnp.sum(kw, axis=0)[None, :]
    m_ref[...] = jnp.full_like(m_ref, m_new)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk(q, k, v, logi, logf, *, chunk: int = 64,
                interpret: Optional[bool] = None):
    """q/k/v (B, NH, S, dh); logi/logf (B, NH, S) -> h (B, NH, S, dh)."""
    B, NH, S, dh = q.shape
    L = min(chunk, S)
    n_s = S // L
    qr = q.reshape(B * NH, S, dh)
    kr = k.reshape(B * NH, S, dh)
    vr = v.reshape(B * NH, S, dh)
    lir = logi.reshape(B * NH, S, 1)
    lfr = logf.reshape(B * NH, S, 1)

    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, L=L),
        grid=(B * NH, n_s),
        in_specs=[
            pl.BlockSpec((1, L, dh), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, L, dh), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, L, dh), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, L, 1), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, L, 1), lambda bh, s: (bh, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, dh), lambda bh, s: (bh, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B * NH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),  # C
            pltpu.VMEM((1, dh), jnp.float32),   # n
            pltpu.VMEM((1, 1), jnp.float32),    # m
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(qr, kr, vr, lir, lfr)
    return out.reshape(B, NH, S, dh)
