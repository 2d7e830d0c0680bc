"""Production mesh construction and ShardCtx wiring.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The single-pod mesh is
(data=16, model=16) = 256 chips; the multi-pod mesh adds a leading pod axis:
(pod=2, data=16, model=16) = 512 chips, where "pod" is pure data parallelism
across ICI/DCN pod boundaries (parameters are replicated across pods, batch
is sharded over pod x data).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.models.common import ShardCtx


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for CI-scale sharding tests (run under forced host devices)."""
    return _auto_mesh(shape, axes)


def make_shard_ctx(mesh: Optional[jax.sharding.Mesh],
                   seq_parallel: bool = False,
                   flat_dp: bool = False,
                   shard_lstm_r: bool = False) -> ShardCtx:
    """flat_dp: treat the model axis as extra data parallelism (and ZeRO-
    shard parameters over data x model).  The right layout for models too
    small to tensor-parallelize (e.g. xlstm-1.3b on a 256-chip pod), where
    TP would replicate all attention-free compute 16x."""
    if mesh is None:
        return ShardCtx.null()
    axes = mesh.axis_names
    if flat_dp:
        return ShardCtx(
            mesh=mesh,
            dp_axes=tuple(a for a in ("pod", "data", "model") if a in axes),
            tp_axis=None,
            fsdp_axis=tuple(a for a in ("data", "model") if a in axes),
            seq_parallel=False,
            shard_lstm_r=shard_lstm_r,
        )
    dp = tuple(a for a in ("pod", "data") if a in axes)
    return ShardCtx(
        mesh=mesh,
        dp_axes=dp,
        tp_axis="model" if "model" in axes else None,
        fsdp_axis="data" if "data" in axes else None,
        seq_parallel=seq_parallel,
        shard_lstm_r=shard_lstm_r,
    )
