"""Host-facing wrapper: pads shapes to kernel-friendly sizes and dispatches.

``parzen_logdens`` scores unpadded numpy inputs through the Pallas kernel
(compiled on a TPU, interpreted on the CPU: ``repro.kernels.interpret_mode``),
matching ``TPEStrategy``'s numpy ``_log_kde`` oracle.  The fused
proposal program (``repro.core.tpe.fused_tpe_propose``) calls the raw
kernels directly with pre-padded buffers, like ``gp.fused_propose_pallas``
does for the ``gp_acquisition`` suite.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tpe_kde.ref import scott_bandwidth
from repro.kernels.tpe_kde.tpe_kde import parzen_logdens_pallas


def pad_dims(d: int) -> int:
    """Lane-pad the encoded dim (>= 8, multiple of 8)."""
    return max(8, int(math.ceil(d / 8)) * 8)


def pad_rows(n: int, multiple: int) -> int:
    return max(multiple, int(math.ceil(n / multiple)) * multiple)


def parzen_logdens(cands, pts, *, bw=None, block_s: int = 256):
    """(m,) product-Parzen log-density of cands (m, d) under pts (n, d).

    ``bw`` defaults to the Scott-rule bandwidth the TPE strategy uses
    (count/dim-dependent scalar).  Pads m to a block multiple, d to a lane
    multiple, and n to a sublane multiple; padded rows carry weight 0 and
    padded dims are never iterated, so padding is exact.
    """
    cands = np.asarray(cands, np.float32)
    pts = np.asarray(pts, np.float32)
    m, d = cands.shape
    n = pts.shape[0]
    dp = pad_dims(d)
    mp = pad_rows(m, block_s)
    npad = pad_rows(n, 8)
    cb = np.zeros((mp, dp), np.float32)
    cb[:m, :d] = cands
    xb = np.zeros((npad, dp), np.float32)
    xb[:n, :d] = pts
    w = np.zeros(npad, np.float32)
    w[:n] = 1.0
    if bw is None:
        bw = float(jax.device_get(scott_bandwidth(jnp.float32(n), d)))
    inv2bw2 = np.float32(0.5 / (float(bw) ** 2))
    scal = np.array([[inv2bw2, 1.0 / max(n, 1), 0.0, 0.0]], np.float32)
    out = parzen_logdens_pallas(
        jnp.asarray(cb), jnp.asarray(xb), jnp.asarray(w),
        jnp.asarray(scal), d_true=d, block_s=block_s)
    return jax.device_get(out)[:m]
