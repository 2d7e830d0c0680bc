import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * jax.jit(step, in_shardings=..., out_shardings=...).lower(**abstract)
  * .compile() under the production mesh (16x16 single-pod / 2x16x16 multi-pod)
  * memory_analysis() -> fits-per-device evidence
  * cost_analysis() + HLO collective parse -> roofline terms (§Roofline)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k \
      --mesh single [--seq-parallel] [--remat full] [--micro 0] [--ep] \
      [--banded] [--tag baseline]
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_shape, cells
from repro.launch import hlo_analysis, hlo_cost
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh, make_shard_ctx
from repro.launch.sharding import (batch_specs, cache_specs, param_specs,
                                   to_shardings)
from repro.models.common import Runtime
from repro.train.step import (TrainHyper, auto_microbatches, init_train_state,
                              make_decode_step, make_prefill_step,
                              make_train_step)

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def make_runtime(cfg, mesh, args) -> Runtime:
    sc = make_shard_ctx(mesh, seq_parallel=args.seq_parallel,
                        flat_dp=getattr(args, "flat_dp", False),
                        shard_lstm_r=getattr(args, "shard_r", False))
    return Runtime(
        sc=sc,
        attn_q_chunk=args.attn_q_chunk,
        attn_banded=args.banded,
        attn_fallback=getattr(args, "attn_fallback", "kvseq"),
        lstm_bf16_states=getattr(args, "lstm_bf16", False),
        remat_policy=args.remat,
        moe_expert_parallel=args.ep,
        moe_capacity_factor=args.capacity_factor,
        ssm_chunk=args.ssm_chunk,
        ce_chunk=args.ce_chunk,
    )


def lower_cell(arch: str, shape_id: str, mesh_kind: str, args):
    cfg = get_config(arch)
    shape = get_shape(shape_id)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rt = make_runtime(cfg, mesh, args)
    sc = rt.sc
    B = shape.global_batch

    state_sds = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, rt))
    sc_params = sc
    if getattr(args, "serve_tp", False) and shape.kind != "train":
        # serving layout: TP-shard weights, replicate over data/pod — no
        # per-step FSDP all-gathers (there is no optimizer state to shard)
        sc_params = dataclasses.replace(sc, fsdp_axis=None)
    p_specs = param_specs(state_sds["params"], cfg, sc_params,
                          expert_parallel=args.ep)
    p_sh = to_shardings(p_specs, mesh)
    ins = input_specs(cfg, shape, rt)
    meta = {"arch": arch, "shape": shape_id, "mesh": mesh_kind,
            "n_devices": mesh.devices.size,
            "config": {k: v for k, v in vars(args).items()
                       if k in ("seq_parallel", "remat", "micro", "ep",
                                "banded", "attn_q_chunk", "capacity_factor",
                                "ssm_chunk", "ce_chunk", "tag", "flat_dp",
                                "attn_fallback", "lstm_bf16", "serve_tp", "zero1")}}

    if shape.kind == "train":
        n_micro = args.micro or auto_microbatches(cfg, shape, rt)
        meta["n_microbatches"] = n_micro
        hyper = TrainHyper()
        step = make_train_step(cfg, rt, hyper, n_microbatches=n_micro)
        if getattr(args, "zero1", False):
            # ZeRO-1: bf16 params replicated over data (no per-microbatch
            # weight regathers); only fp32 optimizer moments are FSDP-sharded
            sc_repl = dataclasses.replace(sc, fsdp_axis=None)
            p_specs = param_specs(state_sds["params"], cfg, sc_repl,
                                  expert_parallel=args.ep)
            p_sh = to_shardings(p_specs, mesh)
            m_specs = param_specs(state_sds["params"], cfg, sc,
                                  expert_parallel=args.ep)
            msh = to_shardings(m_specs, mesh)
            opt_sh = {"m": msh, "v": msh, "step": NamedSharding(mesh, P())}
        else:
            opt_sh = {"m": p_sh, "v": p_sh,
                      "step": NamedSharding(mesh, P())}
        state_sh = {"params": p_sh, "opt": opt_sh}
        b_sh = to_shardings(batch_specs(ins["batch"], sc, B), mesh)
        metric_keys = ("loss", "ce", "tokens", "moe_lb_loss", "moe_router_z",
                       "moe_drop_frac", "grad_norm", "lr")
        m_sh = {k: NamedSharding(mesh, P()) for k in metric_keys}
        jitted = jax.jit(step, in_shardings=(state_sh, b_sh),
                         out_shardings=(state_sh, m_sh), donate_argnums=0)
        lower_args = (state_sds, ins["batch"])
    elif shape.kind == "prefill":
        from repro.models.transformer import init_cache
        step = make_prefill_step(cfg, rt, cache_size=shape.seq_len)
        cache_sds = jax.eval_shape(
            lambda: init_cache(cfg, rt, B, shape.seq_len))
        c_sh = to_shardings(cache_specs(cache_sds, cfg, sc, B), mesh)
        b_sh = to_shardings(batch_specs(ins["batch"], sc, B), mesh)
        tok_sh = NamedSharding(mesh, P(sc.div(B, sc.dp_axes)))
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(tok_sh, c_sh))
        lower_args = (state_sds["params"], ins["batch"])
    else:  # decode
        step = make_decode_step(cfg, rt)
        c_specs = cache_specs(ins["cache"], cfg, sc, B)
        c_sh = to_shardings(c_specs, mesh)
        bspec = sc.div(B, sc.dp_axes)
        tok_in_sh = NamedSharding(mesh, P(bspec, None))
        tok_sh = NamedSharding(mesh, P(bspec))
        len_sh = NamedSharding(mesh, P())
        jitted = jax.jit(step, in_shardings=(p_sh, tok_in_sh, c_sh, len_sh),
                         out_shardings=(tok_sh, c_sh), donate_argnums=2)
        lower_args = (state_sds["params"], ins["tokens"], ins["cache"],
                      ins["cache_len"])

    t0 = time.time()
    with mesh:
        lowered = jitted.lower(*lower_args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    meta.update(t_lower_s=round(t_lower, 2), t_compile_s=round(t_compile, 2))
    return cfg, shape, mesh, compiled, meta


def analyze(cfg, shape, mesh, compiled, meta) -> dict:
    n_dev = mesh.devices.size
    ca = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}

    hlo = compiled.as_text()
    if meta.get("save_hlo"):
        import gzip
        p = Path(meta["save_hlo"])
        p.parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(p, "wt") as f:
            f.write(hlo)
    # Loop-aware static analysis (XLA cost_analysis counts scan bodies once).
    mod = hlo_cost.analyze_module(hlo, n_dev)
    flops = mod["flops"]
    hbm_bytes = mod["bytes"]
    coll = mod["coll"]
    wire = sum(s["wire_bytes"] for s in coll.values())
    terms = hlo_analysis.roofline_terms(flops, hbm_bytes, wire)

    # useful-FLOPs ratio
    model_flops = hlo_analysis.model_flops(cfg, shape)
    meta.update(
        flops_per_chip=flops,
        hbm_bytes_per_chip=hbm_bytes,
        wire_bytes_per_chip=wire,
        collectives={k: v for k, v in coll.items() if v["count"]},
        memory_analysis=mem_info,
        model_flops=model_flops,
        hlo_flops_global=flops * n_dev,
        useful_flops_ratio=(model_flops / (flops * n_dev)
                           if flops else None),
        xla_cost_analysis={"flops_body_once": float(ca.get("flops", 0.0)),
                           "bytes_body_once": float(
                               ca.get("bytes accessed", 0.0))},
        roofline=terms,
        breakdown=mod.get("breakdown", []),
        hlo_text_bytes=len(hlo),
    )
    return meta


def run_cell(arch, shape_id, mesh_kind, args) -> dict:
    cfg, shape, mesh, compiled, meta = lower_cell(arch, shape_id, mesh_kind,
                                                  args)
    if getattr(args, "save_hlo", False):
        meta["save_hlo"] = str(
            Path(args.out) / "hlo"
            / f"{arch}__{shape_id}__{mesh_kind}__{args.tag}.hlo.gz")
    meta = analyze(cfg, shape, mesh, compiled, meta)
    print(compiled.memory_analysis())
    return meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=["none", "dots", "full"])
    ap.add_argument("--micro", type=int, default=0)
    ap.add_argument("--ep", action="store_true")
    ap.add_argument("--banded", action="store_true")
    ap.add_argument("--flat-dp", action="store_true",
                    help="model axis becomes extra DP + ZeRO (small archs)")
    ap.add_argument("--attn-fallback", default="kvseq",
                    choices=["kvseq", "qseq"])
    ap.add_argument("--lstm-bf16", action="store_true",
                    help="stash xLSTM scan outputs in bf16")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--serve-tp", action="store_true",
                    help="serving layout: replicate params over data axes")
    ap.add_argument("--zero1", action="store_true",
                    help="replicate bf16 params over data; shard only moments")
    ap.add_argument("--shard-r", action="store_true",
                    help="FSDP-shard sLSTM recurrent weights (chunked scan)")
    ap.add_argument("--attn-q-chunk", type=int, default=512)
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--ssm-chunk", type=int, default=256)
    ap.add_argument("--ce-chunk", type=int, default=512)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default=str(ARTIFACT_DIR))
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        todo = [(a, s) for a, s, ok, _ in cells(include_skips=False)]
    else:
        todo = [(args.arch, args.shape)]

    failures = 0
    for arch, shape_id in todo:
        for mesh_kind in meshes:
            name = f"{arch}__{shape_id}__{mesh_kind}__{args.tag}"
            path = outdir / f"{name}.json"
            try:
                t0 = time.time()
                meta = run_cell(arch, shape_id, mesh_kind, args)
                meta["t_total_s"] = round(time.time() - t0, 2)
                path.write_text(json.dumps(meta, indent=2, default=str))
                r = meta["roofline"]
                print(f"OK   {name}: compute={r['t_compute_s']:.4f}s "
                      f"mem={r['t_memory_s']:.4f}s coll={r['t_collective_s']:.4f}s "
                      f"dominant={r['dominant']} "
                      f"frac={r['roofline_fraction']:.3f}", flush=True)
            except Exception as e:
                failures += 1
                path.with_suffix(".err").write_text(
                    f"{e}\n{traceback.format_exc()}")
                print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
