"""jit'd wrapper for the selective-scan kernel."""
from __future__ import annotations

from repro.kernels.ssm_scan.ssm_scan import ssm_scan


def selective_scan(Abar, Bx, Cc, *, block_d=512, chunk=64):
    return ssm_scan(Abar, Bx, Cc, block_d=block_d, chunk=chunk)
