"""jit'd wrapper for the selective-scan kernel."""
from __future__ import annotations

from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.ssm_scan.ssm_scan import ssm_scan


def selective_scan(Abar, Bx, Cc, *, use_pallas=True,
                   block_d=512, chunk=64):
    if use_pallas:
        return ssm_scan(Abar, Bx, Cc, block_d=block_d, chunk=chunk)
    return ssm_scan_ref(Abar, Bx, Cc)
