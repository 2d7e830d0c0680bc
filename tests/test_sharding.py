"""Sharded lowering + elastic-restore + compressed-psum tests.

These need >1 device, so each spawns a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count set BEFORE jax imports
(the main test process must keep seeing 1 device).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, n_dev: int = 8) -> str:
    # JAX_PLATFORMS=cpu: these are forced-host-device simulations; without
    # it a stripped env lets the TPU PJRT plugin probe GCP instance metadata
    # (30 retries per variable) and the subprocess blows its timeout.
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_reduced_arch_lowers_on_mesh():
    """jit(train_step) with full sharding rules compiles on a (2,4) mesh
    and the loop-aware HLO analyzer sees its collectives."""
    out = _run("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_test_mesh, make_shard_ctx
        from repro.launch.sharding import param_specs, batch_specs, to_shardings
        from repro.launch import hlo_cost
        from repro.models.common import Runtime
        from repro.train.step import TrainHyper, init_train_state, make_train_step
        import dataclasses

        mesh = make_test_mesh((2, 4), ("data", "model"))
        cfg = get_config("smollm-135m", reduced=True)
        cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=4, d_model=64,
                                  d_ff=128, vocab_size=512)
        rt = Runtime(sc=make_shard_ctx(mesh), ce_chunk=16)
        state = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg, rt))
        ps = to_shardings(param_specs(state["params"], cfg, rt.sc), mesh)
        sh = {"params": ps, "opt": {"m": ps, "v": ps, "step": NamedSharding(mesh, P())}}
        B, S = 8, 32
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        bs = to_shardings(batch_specs(batch, rt.sc, B), mesh)
        step = make_train_step(cfg, rt, TrainHyper(), 2)
        lowered = jax.jit(step, in_shardings=(sh, bs), donate_argnums=0).lower(state, batch)
        compiled = lowered.compile()
        res = hlo_cost.analyze_module(compiled.as_text(), 8)
        coll = {k: v["count"] for k, v in res["coll"].items() if v["count"]}
        print(json.dumps({"flops": res["flops"], "coll": coll}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["flops"] > 0
    assert sum(res["coll"].values()) > 0  # TP/FSDP produced collectives


def test_elastic_remesh_restore(tmp_path):
    """Checkpoint saved while sharded on (4,2) restores onto (2,2,2)."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import Checkpointer
        from repro.launch.mesh import make_test_mesh

        mesh1 = make_test_mesh((4, 2), ("data", "model"))
        state = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        sh1 = {{"w": NamedSharding(mesh1, P("data", "model"))}}
        state = jax.device_put(state, sh1)
        ck = Checkpointer({str(tmp_path)!r}, async_save=False)
        ck.save(1, state)

        mesh2 = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        sh2 = {{"w": NamedSharding(mesh2, P(("pod", "data"), "model"))}}
        restored, _ = ck.restore(None, state, shardings=sh2)
        assert restored["w"].sharding == sh2["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64).reshape(8, 8))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_compressed_psum_shard_map():
    """int8 EF all-reduce over a manual 'pod' axis matches fp32 psum."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax import shard_map
        from repro.launch.mesh import make_test_mesh
        from repro.optim.compression import compressed_psum
        from jax.sharding import PartitionSpec as P

        mesh = make_test_mesh((4,), ("pod",))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32)),
                        jnp.float32)

        @partial(shard_map, mesh=mesh, in_specs=P("pod"),
                 out_specs=P("pod"))
        def f(xs):
            return compressed_psum(xs[0], "pod")[None]

        got = np.asarray(f(x))[0]
        want = np.asarray(x.sum(0))
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < 0.02, err
        print("PSUM_OK", err)
    """, n_dev=4)
    assert "PSUM_OK" in out


def test_production_mesh_shapes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert m1.devices.size == 256 and m1.axis_names == ("data", "model")
        assert m2.devices.size == 512 and m2.axis_names == ("pod", "data", "model")
        print("MESH_OK")
    """, n_dev=512)
    assert "MESH_OK" in out
