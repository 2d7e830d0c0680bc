"""The readers of the factor and fit programs' device time: the mean of
their executions on a small trace recorded on a TPU v5e, on a hand-made
one, and nothing where the program did not run."""
import json
from pathlib import Path

import pytest

from bench.lib.registry import Registry

REPO = Path(__file__).resolve().parents[2]
SLICE = Path(__file__).resolve().parent / "data" / "xgb32_zipf_slice.json"
READERS = {"factors_ms.tail": "bank_factors",
           "fit_device_ms.tail": "fit_hypers_bank"}


def _read(name, events):
    return Registry(REPO).reader(name)({"events": events, "window_ns": 1e9,
                                        "peaks": {}})


@pytest.mark.parametrize("name", sorted(READERS))
def test_mean_over_the_recorded_slice(name):
    events = json.loads(SLICE.read_text())
    d = [e["d"] for e in events if e["plane"] == "device"
         and e["line"] == "XLA Modules"
         and e["name"].startswith(f"jit_{READERS[name]}(")]
    assert len(d) >= 3
    assert _read(name, events) == pytest.approx(sum(d) / len(d) / 1e6,
                                                 rel=1e-12)


def test_hand_made_trace():
    ev = [{"plane": "device", "device": "d0", "line": "XLA Modules",
           "name": "jit_bank_factors(3)", "t": 0.0, "d": 1.2e8},
          {"plane": "device", "device": "d0", "line": "XLA Modules",
           "name": "jit_bank_factors(3)", "t": 5e8, "d": 0.8e8},
          {"plane": "device", "device": "d0", "line": "XLA Ops",
           "name": "cholesky", "t": 0.0, "d": 9e8},
          {"plane": "device", "device": "d0", "line": "XLA Modules",
           "name": "jit_fit_hypers_bank(7)", "t": 2e8, "d": 2.5e8},
          {"plane": "host", "device": "h", "line": "python",
           "name": "bench.fit", "t": 1e8, "d": 4e8}]
    assert _read("factors_ms.tail", ev) == pytest.approx(100.0)
    assert _read("fit_device_ms.tail", ev) == pytest.approx(250.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_absent_program_gives_nothing(name):
    other = [{"plane": "device", "device": "d0", "line": "XLA Modules",
              "name": "jit_bank_pick(1)", "t": 0.0, "d": 4e8}]
    assert _read(name, []) is None
    assert _read(name, other) is None
