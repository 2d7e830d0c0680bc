"""Batched serving driver: prefill a prompt batch, then greedy decode.

On the CPU container run reduced configs; on TPU the same driver runs under
``make_production_mesh()`` with the serving param layout (TP-sharded weights
replicated over the data axis — see launch/sharding.py + EXPERIMENTS §Perf).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.common import Runtime
from repro.models.transformer import init_params
from repro.train.step import make_decode_step, make_prefill_step


def run(args) -> dict:
    cfg = get_config(args.arch, reduced=args.reduced)
    rt = Runtime(param_dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
                 compute_dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
                 pallas=args.pallas)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg, rt)

    B, P = args.batch, args.prompt_len
    total = P + args.gen
    batch = {"tokens": jax.random.randint(key, (B, P), 0, cfg.vocab_size,
                                          jnp.int32)}
    if cfg.vision_tokens:
        batch["patches"] = jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model), rt.compute_dtype)
        total += cfg.vision_tokens
    if cfg.encoder_layers:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), rt.compute_dtype)

    prefill = jax.jit(make_prefill_step(cfg, rt, cache_size=total))
    decode = jax.jit(make_decode_step(cfg, rt), donate_argnums=2)

    t0 = time.time()
    tok, cache = prefill(params, batch)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    out_tokens = [np.asarray(tok)]
    pos0 = P + (cfg.vision_tokens or 0)
    t0 = time.time()
    for i in range(args.gen - 1):
        tok, cache = decode(params, tok[:, None], cache,
                            jnp.int32(pos0 + i))
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.stack(out_tokens, axis=1)
    result = {
        "arch": args.arch,
        "prefill_s": round(t_prefill, 4),
        "decode_s": round(t_decode, 4),
        "decode_tok_s": round(B * (args.gen - 1) / max(t_decode, 1e-9), 1),
        "generated_shape": list(gen.shape),
        "sample": gen[0, :10].tolist(),
    }
    return result


def make_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    return ap


if __name__ == "__main__":
    print(json.dumps(run(make_parser().parse_args()), indent=2))
