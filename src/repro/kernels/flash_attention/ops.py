"""jit'd wrapper with layout adaptation for the model's (B, S, H, hd)."""
from __future__ import annotations

from repro.kernels.flash_attention.flash_attention import flash_attention


def sdpa(q_bshd, k_bskd, v_bskd, *, causal=True, block_q=128, block_k=128):
    """Model-layout entry: q (B, Sq, H, hd), k/v (B, Sk, KV, hd)."""
    q = q_bshd.swapaxes(1, 2)
    k = k_bskd.swapaxes(1, 2)
    v = v_bskd.swapaxes(1, 2)
    out = flash_attention(q, k, v, causal=causal,
                          block_q=block_q, block_k=block_k)
    return out.swapaxes(1, 2)
