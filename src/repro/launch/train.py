"""End-to-end training driver.

Runs real optimization steps (synthetic Markov LM data) with checkpointing,
resume, and metrics logging.  On this CPU container use ``--reduced`` (or
--arch smollm-135m with small batch/seq overrides); on a TPU fleet the same
driver runs the full configs under ``make_production_mesh()``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 50
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 100 --resume --ckpt-dir /tmp/ckpt       # crash-restart drill
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.common import Runtime
from repro.optim.adamw import AdamWConfig
from repro.train.checkpoint import Checkpointer
from repro.train.step import TrainHyper, init_train_state, make_train_step


def build(args):
    cfg = get_config(args.arch, reduced=args.reduced)
    rt = Runtime(
        param_dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
        compute_dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
        ce_chunk=min(args.seq, 512),
        ssm_chunk=min(args.seq, 256),
        remat_policy=args.remat,
        pallas=args.pallas,
    )
    hyper = TrainHyper(
        opt=AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                        total_steps=args.steps,
                        weight_decay=args.weight_decay),
        grad_compression=args.grad_compression,
    )
    return cfg, rt, hyper


def run(args) -> dict:
    cfg, rt, hyper = build(args)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch,
                                  seed=args.data_seed))
    state = init_train_state(jax.random.PRNGKey(args.seed), cfg, rt,
                             grad_compression=hyper.grad_compression)
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    start_step = 0
    ckpt = Checkpointer(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, meta = ckpt.restore(None, state)
        start_step = meta["step"]
        data.restore(meta["data_state"])
        print(f"resumed from step {start_step}")

    step_fn = jax.jit(
        make_train_step(cfg, rt, hyper, n_microbatches=args.micro),
        donate_argnums=0)

    log_path = Path(args.log) if args.log else None
    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        data.step = step + 1
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if log_path:
            with open(log_path, "a") as f:
                f.write(json.dumps(
                    {"step": step, "loss": loss,
                     "ce": float(metrics["ce"]),
                     "grad_norm": float(metrics["grad_norm"]),
                     "lr": float(metrics["lr"])}) + "\n")
        if args.verbose and (step % args.print_every == 0
                             or step == args.steps - 1):
            tok_s = (args.batch * args.seq * (step - start_step + 1)
                     / max(time.time() - t0, 1e-9))
            print(f"step {step:5d} loss {loss:7.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"lr {float(metrics['lr']):.2e} tok/s {tok_s:,.0f}",
                  flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state,
                      extra={"data_state": data.state(),
                             "arch": args.arch, "loss": loss})
    if ckpt:
        ckpt.save(args.steps, state, extra={"data_state": data.state(),
                                            "arch": args.arch,
                                            "loss": losses[-1]})
        ckpt.wait()
    return {"final_loss": losses[-1] if losses else float("nan"),
            "first_loss": losses[0] if losses else float("nan"),
            "n_params": n_params,
            "losses": losses,
            "wall_s": time.time() - t0}


def make_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=1234)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log", default="")
    ap.add_argument("--print-every", type=int, default=10)
    ap.add_argument("--verbose", action="store_true", default=True)
    return ap


if __name__ == "__main__":
    out = run(make_parser().parse_args())
    print(json.dumps({k: v for k, v in out.items() if k != "losses"}))
