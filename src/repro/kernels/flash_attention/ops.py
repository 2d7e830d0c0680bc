"""jit'd wrapper with layout adaptation for the model's (B, S, H, hd)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def sdpa(q_bshd, k_bskd, v_bskd, *, causal=True,
         use_pallas=True, block_q=128, block_k=128):
    """Model-layout entry: q (B, Sq, H, hd), k/v (B, Sk, KV, hd)."""
    q = q_bshd.swapaxes(1, 2)
    k = k_bskd.swapaxes(1, 2)
    v = v_bskd.swapaxes(1, 2)
    if use_pallas:
        out = flash_attention(q, k, v, causal=causal,
                              block_q=block_q, block_k=block_k)
    else:
        out = attention_ref(q, k, v, causal=causal)
    return out.swapaxes(1, 2)
