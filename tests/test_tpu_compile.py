"""Compile the served path's device programs for a described TPU v5e.

No chip is attached: the TPU compiler that ships with ``libtpu`` compiles
for a topology described by name, and refuses what the chip would refuse
(misaligned slices, more VMEM than a kernel may use, programs that do not
fit the device).  Shapes are the ones ``chip_smoke.py`` serves: the
XGBoost-style 10-parameter space (12 encoded dims, padded to 16 lanes,
28800 candidates per ask of 4), observation buckets of 512 and 1024.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
BLOCK_S = 256


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "_chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def served():
    """(d, dp, S) of the smoke's space."""
    from repro.core.spaces import ParamSpace
    from repro.kernels.tpe_kde.ops import pad_dims
    from repro.service.server import space_from_spec
    cs = _chip_smoke()
    space = ParamSpace(space_from_spec(cs.SPACE))
    return space.dim, pad_dims(space.dim), space.mc_samples(
        cs.FLEET["ask_n"])


def _spec(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    print(compiled.memory_analysis())
    return compiled


@pytest.mark.parametrize("n", [512, 1024])
@pytest.mark.parametrize("kernel", ["score_cov_pallas", "var_downdate_pallas",
                                    "tpe_scores_pallas",
                                    "parzen_logdens_pallas"])
def test_kernel_compiles_for_v5e(one_chip, served, kernel, n):
    from repro.kernels.gp_acquisition import gp_acquisition as gpa
    from repro.kernels.tpe_kde import tpe_kde

    d, dp, S = served
    Sp = -(-S // BLOCK_S) * BLOCK_S

    def sp(*shape):
        return _spec(one_chip, shape)

    if kernel == "score_cov_pallas":
        compiled = _compile(
            lambda *a: gpa.score_cov_pallas(*a, block_s=BLOCK_S,
                                            interpret=False),
            sp(Sp, dp), sp(n, dp), sp(n), sp(n, n), sp(n), sp(), sp())
    elif kernel == "var_downdate_pallas":
        compiled = _compile(
            lambda *a: gpa.var_downdate_pallas(*a, block_s=BLOCK_S,
                                               interpret=False),
            sp(Sp, dp), sp(dp), sp(Sp, n), sp(n), sp(), sp(Sp), sp())
    elif kernel == "tpe_scores_pallas":
        compiled = _compile(
            lambda *a: tpe_kde.tpe_scores_pallas(
                *a, d_true=d, block_s=BLOCK_S, interpret=False),
            sp(Sp, dp), sp(n, dp), sp(n, dp), sp(n), sp(n), sp(1, 4))
    else:
        compiled = _compile(
            lambda *a: tpe_kde.parzen_logdens_pallas(
                *a, d_true=d, block_s=BLOCK_S, interpret=False),
            sp(Sp, dp), sp(n, dp), sp(n), sp(1, 4))
    assert "tpu_custom_call" in compiled.as_text()


# one study per served ask, and the widest family of the fleet batch
@pytest.mark.parametrize("rows", [1, 11])
def test_bank_pick_compiles_for_v5e(one_chip, served, rows):
    from repro.core import gp

    d, dp, S = served
    na = 512

    def sp(*shape):
        return _spec(one_chip, shape)

    big = sp(rows, S, na)
    compiled = gp.bank_pick.lower(
        big, big, big, sp(rows, S, dp), sp(rows, na), sp(rows, na),
        sp(rows, na, na), sp(rows, na, na), sp(rows), sp(rows), sp(rows),
        sp(), batch_size=4, S=S).compile()
    mem = compiled.memory_analysis()
    print(mem)
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    reckoned = _chip_smoke().reckon_pick_bytes(rows, S, na)
    assert held <= reckoned * 1.05
    assert held < 16e9


def test_fit_hypers_bank_compiles_for_v5e(one_chip, served):
    """The fleet's GP-family rows (21 of 32 studies) at na = 512."""
    from repro.core import gp

    d, _, _ = served
    rows, na = 21, 512

    def sp(*shape):
        return _spec(one_chip, shape)

    compiled = gp.fit_hypers_bank.lower(
        sp(rows, na, d), sp(rows, na), sp(rows, na), sp(rows, d),
        sp(rows), sp(rows), sp(rows), sp(rows), steps=40).compile()
    print(compiled.memory_analysis())
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
