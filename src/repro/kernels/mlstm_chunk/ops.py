"""jit'd wrapper for the chunkwise mLSTM kernel."""
from __future__ import annotations

from repro.kernels.mlstm_chunk.mlstm_chunk import mlstm_chunk


def mlstm_mixer(q, k, v, logi, logf, *, chunk=64):
    return mlstm_chunk(q, k, v, logi, logf, chunk=chunk)
