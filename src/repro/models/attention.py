"""GQA attention: dense, blockwise (long-context), decode, and cross variants.

Sharding strategy (best-effort, per ShardCtx.div):
  * heads divisible by TP  -> shard the head axis of q/scores ("model").
  * heads NOT divisible    -> shard the KV-sequence axis of k/v/scores instead
    (yi-34b 56H, smollm 9H, whisper 20H); softmax over the sharded axis is
    handled by SPMD partial-max/sum all-reduces (small (B,H,Sq) tensors).
  * KV heads are kept replicated over TP when not divisible (GQA kv=8 vs
    TP=16); the repeat-to-H materialization is sliced for free when the head
    axis is sharded.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Runtime, apply_rope, dense_init, rope_tables


def attn_init(key, cfg: ArchConfig, rt: Runtime) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, (d, H * hd), rt.param_dtype),
        "wk": dense_init(ks[1], d, (d, KV * hd), rt.param_dtype),
        "wv": dense_init(ks[2], d, (d, KV * hd), rt.param_dtype),
        "wo": dense_init(ks[3], H * hd, (H * hd, d), rt.param_dtype),
    }


def _project_qkv(p, x, kv_x, cfg: ArchConfig, rt: Runtime):
    B, Sq, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    cd = rt.compute_dtype
    q = jnp.einsum("bsd,dh->bsh", x.astype(cd), p["wq"].astype(cd))
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dh->bsh", src.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dh->bsh", src.astype(cd), p["wv"].astype(cd))
    q = q.reshape(B, Sq, H, hd)
    k = k.reshape(B, src.shape[1], KV, hd)
    v = v.reshape(B, src.shape[1], KV, hd)
    return q, k, v


def _expand_kv(k: jax.Array, cfg: ArchConfig) -> jax.Array:
    G = cfg.n_heads // cfg.n_kv_heads
    return k if G == 1 else jnp.repeat(k, G, axis=2)


def _shard_plan(cfg: ArchConfig, rt: Runtime):
    """(head_axis, kvseq_axis, qseq_axis): exactly one is non-None under TP.

    heads %% TP == 0 -> shard heads.  Otherwise fall back to sharding a
    sequence axis of the score tensor: "kvseq" (baseline; softmax reduces
    over the sharded axis -> per-layer ARs) or "qseq" (rows of the score
    matrix; softmax stays local, k/v are gathered once — see §Perf yi-34b).
    """
    sc = rt.sc
    h_axis = sc.div(cfg.n_heads, sc.tp_axis)
    if h_axis is not None:
        return h_axis, None, None
    if rt.attn_fallback == "qseq":
        return None, None, sc.tp_axis
    return None, sc.tp_axis, None


def _sdpa_dense(q, k, v, *, causal: bool, cfg, rt: Runtime, B: int):
    """Single-einsum attention; q (B,Sq,H,hd), k/v already H-expanded."""
    sc = rt.sc
    h_axis, kvseq_axis, qseq_axis = _shard_plan(cfg, rt)
    bs = sc.div(B, sc.dp_axes)
    Sq, Sk = q.shape[1], k.shape[1]
    if kvseq_axis is not None:
        k = sc.constrain(k, bs, sc.div(Sk, kvseq_axis), None, None)
        v = sc.constrain(v, bs, sc.div(Sk, kvseq_axis), None, None)
    if qseq_axis is not None:
        q = sc.constrain(q, bs, sc.div(Sq, qseq_axis), None, None)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (cfg.hd ** -0.5)
    scores = sc.constrain(
        scores, bs, h_axis,
        sc.div(Sq, qseq_axis) if qseq_axis else None,
        sc.div(Sk, kvseq_axis) if kvseq_axis else None)
    if causal:
        iq = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        ik = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        scores = jnp.where((ik <= iq + (Sk - Sq))[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(rt.compute_dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return sc.constrain(out, bs, sc.div(Sq, qseq_axis) if qseq_axis
                        else None, h_axis, None)


def _sdpa_blockwise(q, k, v, *, causal: bool, cfg, rt: Runtime, B: int):
    """Scan over q chunks with full-KV online softmax (rematerialized).

    Memory: O(B * H * Cq * Sk) per chunk instead of O(B * H * Sq * Sk).
    FLOPs are counted over the full Sq x Sk rectangle (causal skipping is the
    ``attn_banded`` optimization, see EXPERIMENTS.md §Perf).
    """
    sc = rt.sc
    h_axis, kvseq_axis, qseq_axis = _shard_plan(cfg, rt)
    bs = sc.div(B, sc.dp_axes)
    Sq, Sk = q.shape[1], k.shape[1]
    Cq = min(rt.attn_q_chunk, Sq)
    if Sq % Cq != 0:
        Cq = Sq
    nq = Sq // Cq
    if kvseq_axis is not None:
        k = sc.constrain(k, bs, sc.div(Sk, kvseq_axis), None, None)
        v = sc.constrain(v, bs, sc.div(Sk, kvseq_axis), None, None)

    qs = q.reshape(B, nq, Cq, q.shape[2], q.shape[3]).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(qc, idx):
        if qseq_axis is not None:  # shard the q rows within the chunk
            qc = sc.constrain(qc, bs, sc.div(Cq, qseq_axis), None, None)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, k,
                            preferred_element_type=jnp.float32)
        scores = scores * (cfg.hd ** -0.5)
        scores = sc.constrain(
            scores, bs, h_axis,
            sc.div(Cq, qseq_axis) if qseq_axis else None,
            sc.div(Sk, kvseq_axis) if kvseq_axis else None)
        if causal:
            iq = idx * Cq + jax.lax.broadcasted_iota(jnp.int32, (Cq, Sk), 0)
            ik = jax.lax.broadcasted_iota(jnp.int32, (Cq, Sk), 1)
            scores = jnp.where((ik <= iq)[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(rt.compute_dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        return sc.constrain(out, bs, sc.div(Cq, qseq_axis) if qseq_axis
                            else None, h_axis, None)

    def body(_, inp):
        qc, idx = inp
        return None, chunk(qc, idx)

    _, outs = jax.lax.scan(body, None, (qs, jnp.arange(nq)))
    return outs.swapaxes(0, 1).reshape(B, Sq, q.shape[2], q.shape[3])


def attention(p: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime, *,
              causal: bool = True, positions: Optional[jax.Array] = None,
              kv_x: Optional[jax.Array] = None, return_kv: bool = False):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, Sq, _ = x.shape
    q, k, v = _project_qkv(p, x, kv_x, cfg, rt)
    if cfg.rope and kv_x is None:
        if positions is None:
            positions = jnp.arange(Sq, dtype=jnp.int32)[None, :]
        cos, sin = rope_tables(positions, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    kv = (k, v)  # un-expanded (B, S, KV, hd) for the decode cache
    bq, bk = min(128, q.shape[1]), min(128, k.shape[1])
    divisible = q.shape[1] % bq == 0 and k.shape[1] % bk == 0
    if rt.pallas and rt.sc.mesh is None and divisible:
        # single-device hot path: fused flash-attention kernel (GQA-aware;
        # under a mesh the jnp path lowers through SPMD instead)
        from repro.kernels.flash_attention.ops import sdpa as flash_sdpa
        out = flash_sdpa(q, k, v, causal=causal, block_q=bq, block_k=bk)
    else:
        k = _expand_kv(k, cfg)
        v = _expand_kv(v, cfg)
        if k.shape[1] <= rt.attn_dense_threshold:
            out = _sdpa_dense(q, k, v, causal=causal, cfg=cfg, rt=rt, B=B)
        else:
            out = _sdpa_blockwise(q, k, v, causal=causal, cfg=cfg, rt=rt, B=B)
    cd = rt.compute_dtype
    out = out.reshape(B, Sq, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(cd))
    if return_kv:
        return out, kv
    return out


def attention_with_kv(p: dict, x: jax.Array, cfg: ArchConfig, rt: Runtime, *,
                      causal: bool = True,
                      positions: Optional[jax.Array] = None,
                      kv_x: Optional[jax.Array] = None):
    return attention(p, x, cfg, rt, causal=causal, positions=positions,
                     kv_x=kv_x, return_kv=True)


# --------------------------------------------------------------------------- #
# Decode (one new token against a KV cache)
# --------------------------------------------------------------------------- #
def attn_cache_init(cfg: ArchConfig, rt: Runtime, B: int, S: int) -> dict:
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((B, S, KV, hd), rt.compute_dtype),
        "v": jnp.zeros((B, S, KV, hd), rt.compute_dtype),
    }


def attn_decode(p: dict, x: jax.Array, cache: dict, cache_len: jax.Array,
                cfg: ArchConfig, rt: Runtime,
                cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None
                ) -> Tuple[jax.Array, dict]:
    """x (B, 1, d); cache k/v (B, S, KV, hd); cache_len scalar int32.

    Writes the new k/v at ``cache_len`` and attends over positions
    [0, cache_len].  With ``cross_kv`` set, attends over the precomputed
    encoder k/v instead (no cache update).
    """
    sc = rt.sc
    B = x.shape[0]
    bs = sc.div(B, sc.dp_axes)
    h_axis, _, _ = _shard_plan(cfg, rt)
    # decode: a 1-token q can't be row-sharded; always kv-seq shard the cache
    seq_axis = sc.tp_axis if h_axis is None else None

    if cross_kv is not None:
        q, _, _ = _project_qkv(p, x, jnp.zeros_like(x), cfg, rt)
        k, v = cross_kv
        new_cache = cache
    else:
        q, k_new, v_new = _project_qkv(p, x, None, cfg, rt)
        if cfg.rope:
            pos = jnp.full((B, 1), cache_len, jnp.int32)
            cos, sin = rope_tables(pos, cfg.hd, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k_new = apply_rope(k_new, cos, sin)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, cache_len, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, cache_len, 0, 0))
        new_cache = {"k": k, "v": v}

    S = k.shape[1]
    k_e = _expand_kv(k, cfg)
    v_e = _expand_kv(v, cfg)
    if seq_axis is not None:
        k_e = sc.constrain(k_e, bs, sc.div(S, seq_axis), None, None)
        v_e = sc.constrain(v_e, bs, sc.div(S, seq_axis), None, None)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_e,
                        preferred_element_type=jnp.float32)
    scores = scores * (cfg.hd ** -0.5)
    if cross_kv is None:
        valid = jnp.arange(S)[None, None, None, :] <= cache_len
        scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(rt.compute_dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v_e)
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(rt.compute_dtype))
    return out, new_cache
