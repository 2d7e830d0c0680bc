"""A whole run of a tiny cell on the CPU, the look for a chip skipped:
sound, it is correct; with the timed path broken underneath, it is not."""
import time
from pathlib import Path

import pytest

from bench.lib.cell import run_cell
from bench.lib.registry import Registry
from bench.tests import tiny

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return Registry(tiny.make(tmp_path_factory.mktemp("root")))


def _run(reg, tmp, fault=None, trace=False, seed=4000000007):
    # the traced slice is half the window: long enough on a loaded CPU for
    # whole asks, tells and lock waits to fall inside it
    return run_cell(reg, "tiny.cell", seed, 16.0 if trace else 6.0, trace,
                    device=dict(CPU), t_process=time.monotonic(),
                    out=Path(tmp), fault=fault)


def test_sound_run_is_correct(reg, tmp_path):
    res = _run(reg, tmp_path, trace=True)
    bad = {k: c for k, c in res["checks"].items()
           if c["value"] is None or c["value"] > c["limit"]}
    assert res["correct"] and not bad, bad
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert {"lock_wait_ms.tail", "journal_ms.tail", "obs_stage_ms.tail",
            "draw_ms.tail", "asks_traced"} <= set(m)
    assert m["asks_traced"]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert (tmp_path / "runs" / "tiny.cell" / "spans.json").is_file()
    assert (tmp_path / "runs" / "tiny.cell" / "requests.jsonl").is_file()


@pytest.mark.parametrize("fault,caught", [
    ("answer", ("gp_pick_gap", "cluster_pick_gap", "tpe_pick_gap")),
    ("state", ("tells_lost",)),
    ("journal", ("answers_mismatch", "tells_lost")),
    ("half", ("asks_short",)),
])
def test_broken_path_is_not_correct(reg, tmp_path, fault, caught):
    res = _run(reg, tmp_path, fault=fault)
    hit = [k for k in caught if (res["checks"][k]["value"] or 0)
           > res["checks"][k]["limit"]]
    assert not res["correct"] and hit, res["checks"]
    e2e = res["metrics"]
    assert set(e2e) == {"ask_p90_ms", "tell_p95_ms", "setup_s"}
