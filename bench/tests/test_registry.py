"""The harness finds a configuration, a mix and a metric added as new
files, and the counts of ``bench/work`` agree with hand counts."""
import importlib.util
import json
from pathlib import Path

from bench.lib import registry
from bench.tests import tiny

REPO = Path(__file__).resolve().parents[2]


def test_new_files_are_found(tmp_path):
    reg = registry.Registry(tiny.make(tmp_path))
    assert reg.cell("tiny.cell")["config"] == "tiny"
    assert reg.config("tiny")["studies"] == 3
    assert reg.mix("tiny_mix")["workers"] == 6
    names = [m["name"] for m in reg.per_layer("tiny.cell")]
    assert "asks_traced" in names and "lock_wait_ms.tail" in names
    assert "lock_wait_ms.sat" not in names
    ev = [{"plane": "host", "name": "bench.ask", "t": 0.0, "d": 5.0}] * 3
    assert reg.reader("asks_traced")({"events": ev}) == 3.0
    assert reg.limits("tiny.cell")["sample"]["tpe"] == 1
    assert [m["name"] for m in reg.end_to_end("tiny.cell")] == [
        "ask_p90_ms", "tell_p95_ms", "setup_s"]


def test_every_cell_resolves():
    reg = registry.Registry(REPO)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        assert reg.config(w["config"]) and reg.mix(w["traffic"])
        assert reg.limits(w["name"])["limits"]
        for m in reg.per_layer(w["name"]):
            assert callable(reg.reader(m["name"]))
        moved = {m["moves"] for m in reg.per_layer(w["name"])}
        assert moved <= {m["name"] for m in reg.end_to_end(w["name"])}
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()


def test_suffixed_metric_reads_its_base_reader():
    reg = registry.Registry(REPO)
    assert reg.reader("journal_ms.tail") is not None
    ev = [{"plane": "host", "name": "bench.journal", "t": 0.0, "d": 2e6},
          {"plane": "host", "name": "bench.journal", "t": 9.0, "d": 4e6}]
    assert reg.reader("journal_ms.sat")({"events": ev}) == 3.0
    assert reg.reader("journal_ms.tail")({"events": []}) is None


def _work():
    path = REPO / "bench" / "work" / "gp_pick_chain.py"
    spec = importlib.util.spec_from_file_location("gp_pick_chain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_work_counts_by_hand():
    w = _work()
    # S=2, na=3, d=1, n=2: distances 2*2*3*1 = 12; K L^-T 2*2*3*3 = 36;
    # mean and variance sums 2*2*3 + 2*2*3 = 24; one downdate: 2*2*1 (the
    # kernel row) + 2*2*3 (the product with the cached block) = 16
    assert w.flops(S=2, na=3, d=1, n=2) == 12 + 36 + 24 + 16
    # bytes: candidates 2*1, observations 3*1, two factors 2*9, z and mask
    # 2*3, picks 2 -> 31 float32 words
    assert w.nbytes(S=2, na=3, d=1, n=2) == 4 * 31
    # the served xgb pick: about 61 GFLOP, compute-bound on a v5e
    f = w.flops(S=28800, na=1024, d=12, n=4)
    assert 6.1e10 < f < 6.2e10
    assert f / 197e12 > w.nbytes(S=28800, na=1024, d=12, n=4) / 819e9
