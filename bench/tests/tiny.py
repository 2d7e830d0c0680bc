"""A benchmark root in a temporary directory, holding a copy of the
harness, the program (linked) and one more configuration, mix, metric and
cell, each added as a new file: small enough to run a whole cell on the
CPU."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "studies": 3,
    "space": {"x": {"uniform": [0.0, 1.0]},
              "lr": {"loguniform": [1e-3, 1.0]},
              "k": {"choice": ["a", "b"]}},
    "strategy_cycle": ["bayesian", "tpe", "clustering"], "ask_n": 4,
    "lengths": {"kind": "loguniform", "low": 20, "high": 40},
    "objective": {"base": 1.0, "noise_sd": 0.01, "terms": [
        {"param": "x", "kind": "sq", "center": 0.3, "weight": -1.0},
        {"param": "lr", "transform": "log10", "kind": "sq", "center": -1,
         "weight": -0.1}], "offsets": {"k": {"a": 0.0, "b": -0.05}}},
    "service": {"fit_steps": 10, "refit_every": 8, "compact_every_ops": 0,
                "mc_samples": 64}}
# light enough that a loaded CPU keeps up: short fits, a small bucket
MIX = {"kind": "worker_pools", "workers": 6, "pools": {"kind": "equal"},
       "rate": 1.5, "eval": {"dist": "lognormal", "sigma": 1.0},
       "fail_share": 0.1}
LIMITS = {"sample": {"gp": 2, "cluster": 1, "tpe": 1},
          "limits": {"gp_pick_gap": 1e-3, "cluster_pick_gap": 1e-3,
                     "tpe_pick_gap": 1e-3, "fit_gap": 5e-2,
                     "fit_count_mismatch": 0}}
METRIC = '''"""Asks answered in the traced slice."""
from bench.lib.trace import spans


def read(ctx):
    return float(len(spans(ctx["events"], "ask"))) or None
'''


def make(root: Path) -> Path:
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench/configs/tiny.json").write_text(json.dumps(CONFIG))
    (root / "bench/traffic/tiny_mix.json").write_text(json.dumps(MIX))
    (root / "bench/limits/tiny.cell.json").write_text(json.dumps(LIMITS))
    (root / "bench/metrics/asks_traced.py").write_text(METRIC)
    b["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "test"})
    b["workloads"].append({"name": "tiny.cell", "config": "tiny",
                           "traffic": "tiny_mix", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "xgb32.zipf" in m.get("workloads", ()):
            m["workloads"].append("tiny.cell")
    b["per_layer"].append({"name": "asks_traced", "unit": "asks",
                           "better": "higher", "source": "program_span",
                           "layer": "service", "moves": "ask_p90_ms",
                           "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root
