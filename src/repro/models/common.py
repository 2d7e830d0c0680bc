"""Shared model machinery: sharding context, norms, RoPE, losses, init.

Everything is a pure function over explicit parameter pytrees (no framework).
Sharding is expressed through a ``ShardCtx`` so the same model code runs:
  * un-meshed on CPU for smoke tests (constraints become no-ops),
  * on the (data, model) single-pod mesh,
  * on the (pod, data, model) multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------------------------- #
# Sharding context
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh-aware axis resolution with divisibility fallbacks."""

    mesh: Optional[Mesh] = None
    dp_axes: Tuple[str, ...] = ()     # batch-parallel axes, e.g. ("pod", "data")
    tp_axis: Optional[str] = None     # tensor-parallel axis ("model")
    # parameter-shard axis or tuple of axes ("data" / ("data", "model"))
    fsdp_axis: Optional[object] = None
    seq_parallel: bool = False        # shard activations over seq between blocks
    shard_lstm_r: bool = False        # FSDP-shard sLSTM recurrent weights

    @staticmethod
    def null() -> "ShardCtx":
        return ShardCtx()

    def axis_size(self, axis) -> int:
        if self.mesh is None or axis is None:
            return 1
        if isinstance(axis, (tuple, list)):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    @property
    def tp(self) -> int:
        return self.axis_size(self.tp_axis)

    @property
    def dp(self) -> int:
        return self.axis_size(self.dp_axes) if self.dp_axes else 1

    @property
    def fsdp(self) -> int:
        return self.axis_size(self.fsdp_axis)

    def div(self, n: int, axis):
        """Return ``axis`` if dimension ``n`` is divisible by its mesh size."""
        if self.mesh is None or axis is None:
            return None
        return axis if n % self.axis_size(axis) == 0 else None

    def constrain(self, x: jax.Array, *spec) -> jax.Array:
        """Best-effort ``with_sharding_constraint`` (no-op without a mesh)."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    # Convenience specs -----------------------------------------------------
    def batch_spec(self, n_batch: int):
        return self.div(n_batch, self.dp_axes)

    def act(self, x: jax.Array, batch_dim_size: int, *rest) -> jax.Array:
        """Constrain an activation whose dim 0 is the (global) batch."""
        return self.constrain(x, self.div(batch_dim_size, self.dp_axes), *rest)


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution-policy knobs threaded through the model functions."""

    sc: ShardCtx = dataclasses.field(default_factory=ShardCtx.null)
    param_dtype: jnp.dtype = jnp.bfloat16
    compute_dtype: jnp.dtype = jnp.bfloat16
    accum_dtype: jnp.dtype = jnp.float32
    attn_dense_threshold: int = 8192   # use single-einsum attention below this
    attn_q_chunk: int = 512            # q-chunk for blockwise attention
    attn_banded: bool = False          # exact-causal banded attention (opt)
    attn_fallback: str = "kvseq"       # heads%TP!=0: "kvseq" | "qseq" shard
    lstm_bf16_states: bool = False     # stash xLSTM scan outputs in bf16
    ce_chunk: int = 512                # seq chunk for cross-entropy
    ssm_chunk: int = 256               # chunk length for SSM / mLSTM scans
    moe_capacity_factor: float = 0.0   # 0 -> use cfg.capacity_factor
    moe_expert_parallel: bool = False  # shard expert axis over TP (EP mode)
    remat_policy: str = "full"         # none | dots | full
    pallas: bool = False               # dispatch hot ops to Pallas kernels
    z_loss: float = 1e-4


# --------------------------------------------------------------------------- #
# Norms / activations
# --------------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * scale + bias


def norm_apply(kind: str, x: jax.Array, p: dict) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def norm_init(kind: str, d: int, dtype) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# --------------------------------------------------------------------------- #
# Init helpers
# --------------------------------------------------------------------------- #
def dense_init(key, fan_in: int, shape: Sequence[int], dtype) -> jax.Array:
    std = fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# --------------------------------------------------------------------------- #
# Positions
# --------------------------------------------------------------------------- #
def rope_tables(positions: jax.Array, hd: int, theta: float
                ) -> Tuple[jax.Array, jax.Array]:
    """positions (B, S) -> cos/sin tables (B, S, hd//2) in fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, hd); cos/sin (B, S, hd//2). Interleaved-pair convention."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def sinusoidal_position_at(pos: jax.Array, d: int) -> jax.Array:
    """Single sinusoidal position row; pos scalar int32 -> (d,) fp32."""
    inv = 1.0 / (10_000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])


def sinusoidal_positions(seq: int, d: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10_000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)  # (S, d)


# --------------------------------------------------------------------------- #
# Chunked cross-entropy (never materializes (B, S, V) logits)
# --------------------------------------------------------------------------- #
def chunked_cross_entropy(x: jax.Array, w_head: jax.Array, labels: jax.Array,
                          mask: jax.Array, rt: Runtime, vocab_size: int
                          ) -> Tuple[jax.Array, jax.Array]:
    """Mean CE over masked positions + z-loss.

    x: (B, S, d) final hidden; w_head: (d, Vp) (Vp >= vocab_size, padded rows
    are masked to -inf); labels, mask: (B, S).  Scans over S in ``rt.ce_chunk``
    chunks with rematerialization so the backward pass recomputes each chunk's
    logits instead of saving them.
    """
    B, S, d = x.shape
    Vp = w_head.shape[1]
    C = min(rt.ce_chunk, S)
    n_chunks = S // C if S % C == 0 else 1
    if S % C != 0:
        C = S
    sc = rt.sc

    xs = x.reshape(B, n_chunks, C, d).swapaxes(0, 1)  # (n, B, C, d)
    ls = labels.reshape(B, n_chunks, C).swapaxes(0, 1)
    ms = mask.reshape(B, n_chunks, C).swapaxes(0, 1)

    pad_mask = (jnp.arange(Vp) < vocab_size)

    @jax.checkpoint
    def chunk_loss(xc, lc, mc):
        logits = jnp.einsum("bcd,dv->bcv", xc.astype(rt.compute_dtype),
                            w_head.astype(rt.compute_dtype),
                            preferred_element_type=jnp.float32)
        logits = sc.constrain(logits, sc.div(B, sc.dp_axes), None,
                              sc.div(Vp, sc.tp_axis))
        logits = jnp.where(pad_mask[None, None, :], logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)                 # (B, C)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        ce = (lse - ll) * mc
        zl = jnp.square(lse) * mc
        return ce.sum(), zl.sum()

    def body(carry, inp):
        ce_acc, zl_acc = carry
        xc, lc, mc = inp
        ce, zl = chunk_loss(xc, lc, mc)
        return (ce_acc + ce, zl_acc + zl), None

    (ce_sum, zl_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ls, ms))
    denom = jnp.maximum(mask.sum().astype(jnp.float32), 1.0)
    loss = ce_sum / denom + rt.z_loss * zl_sum / denom
    return loss, denom


def logits_for(x: jax.Array, w_head: jax.Array, rt: Runtime,
               vocab_size: int) -> jax.Array:
    """Full logits for short sequences (decode / smoke tests)."""
    Vp = w_head.shape[1]
    logits = jnp.einsum("bsd,dv->bsv", x.astype(rt.compute_dtype),
                        w_head.astype(rt.compute_dtype),
                        preferred_element_type=jnp.float32)
    if Vp != vocab_size:
        logits = jnp.where(jnp.arange(Vp)[None, None, :] < vocab_size,
                           logits, -1e30)
    return logits
