"""jit'd wrapper for the chunkwise mLSTM kernel."""
from __future__ import annotations

from repro.kernels.mlstm_chunk.mlstm_chunk import mlstm_chunk
from repro.kernels.mlstm_chunk.ref import mlstm_ref


def mlstm_mixer(q, k, v, logi, logf, *, use_pallas=True,
                chunk=64):
    if use_pallas:
        return mlstm_chunk(q, k, v, logi, logf, chunk=chunk)
    return mlstm_ref(q, k, v, logi, logf)
