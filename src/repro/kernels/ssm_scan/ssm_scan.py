"""Pallas TPU kernel: chunked selective-scan recurrence (Mamba).

TPU adaptation: the CUDA reference parallelizes the scan across warps with
shared-memory prefix products; the TPU-native shape is a *chunked time loop
over VMEM-resident channel tiles*:

  grid = (B, di/BD, S/CK)   — the time dimension is sequential ("arbitrary"),
                               the channel dimension is parallel
  scratch = h (BD, N) fp32  — the SSM state persists in VMEM across chunks
  per step: CK sequential VPU updates on the (BD, N) tile, then the
  y = <h, C> contraction accumulates into the (CK, BD) output block.

Sequential-in-time, parallel-in-channel is the right trade on the VPU: each
update is an (BD, N) elementwise FMA, which vectorizes across lanes, while
the O(log S) tree of an associative scan would materialize S x BD x N
intermediates in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssm_kernel(a_ref, b_ref, c_ref, out_ref, h_ref, *, ck: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a = a_ref[0, t]                        # (BD, N)
        b = b_ref[0, t]
        c = c_ref[0, t]                        # (1, N)
        h = a * h + b
        y = jnp.sum(h * c, axis=-1)            # (BD,)
        out_ref[0, t] = y.astype(out_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, ck, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def ssm_scan(Abar, Bx, Cc, *, block_d: int = 512, chunk: int = 64,
             interpret: Optional[bool] = None):
    """Abar/Bx (B, S, di, N) fp32; Cc (B, S, N) -> y (B, S, di) fp32."""
    B, S, di, N = Abar.shape
    block_d = min(block_d, di)
    chunk = min(chunk, S)
    n_d = di // block_d
    n_s = S // chunk

    # layout: (B, S, di, N) -> blocks (1, CK, BD, N); C (1, CK, 1, N)
    out = pl.pallas_call(
        functools.partial(_ssm_kernel, ck=chunk),
        grid=(B, n_d, n_s),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d, N),
                         lambda b, d, s: (b, s, d, 0)),
            pl.BlockSpec((1, chunk, block_d, N),
                         lambda b, d, s: (b, s, d, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, d, s: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda b, d, s: (b, s, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(Abar, Bx, Cc[:, :, None, :])
    return out
