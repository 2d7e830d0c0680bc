"""No result without a TPU, on an unknown device kind, or from a tree
that holds only the benchmark."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.lib.registry import Registry

REPO = Path(__file__).resolve().parents[2]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "xgb32.zipf",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


class _Dev:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True),
                                     ("TPU v99", False)])
def test_device_kind_must_have_peaks(monkeypatch, kind, ok):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_Dev(kind)])
    reg = Registry(REPO)
    if ok:
        assert run.device_info(1, reg)["kind"] == kind
    else:
        with pytest.raises(run.NoChip, match="no entry"):
            run.device_info(1, reg)
    with pytest.raises(run.NoChip, match="4 chips"):
        run.device_info(4, reg)
