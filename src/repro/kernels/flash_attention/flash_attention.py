"""Pallas TPU flash attention (causal, GQA) with online softmax.

TPU adaptation of the FlashAttention GPU algorithm:
  * grid = (B*H, Sq/BQ, Sk/BK); the innermost (KV) grid dimension is
    sequential ("arbitrary") so the (m, l, acc) running statistics live in
    VMEM scratch across KV steps — the TPU analogue of a CUDA thread-block's
    shared-memory accumulators,
  * q/k/v tiles are mapped into VMEM by BlockSpecs; GQA is handled in the
    *index map* (q head h reads kv head h // G) so grouped KV is never
    materialized to H heads in HBM,
  * MXU does the two (BQ, BK) x (BK, hd) matmuls per step; the VPU does the
    online-softmax epilogue in fp32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, scale: float, block_q: int, block_k: int,
                  n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)                  # (BK, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ, BK)
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]                               # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # (BQ, BK)
    corr = jnp.exp(m_prev - m_new)                    # (BQ, 1)
    l_new = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                  # (BK, hd)
    pv = jax.lax.dot(p, v, preferred_element_type=jnp.float32)
    acc_ref[...] = corr * acc_ref[...] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _done():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """q (B, H, Sq, hd); k/v (B, KV, Sk, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    n_q = Sq // block_q
    n_k = Sk // block_k
    scale = hd ** -0.5

    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    qr = q.reshape(B * H, Sq, hd)
    kr = k.reshape(B * KV, Sk, hd)
    vr = v.reshape(B * KV, Sk, hd)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, hd)
