"""The chip benchmark of the tuning service: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process.  It refuses to run without a TPU, or on a device
kind missing from ``bench/peaks.json``.  It builds the cell's deployment
from ``--seed`` (``BENCHMARK.json`` names the configuration and the traffic
mix, each a data file under ``bench/``), serves it in this process over
localhost HTTP, seeds the bank in bulk and warms every shape the window
uses, then offers the mix's load from a child process that never imports
JAX, for ``--seconds``.  After the window it checks what the service
answered (``bench/lib/check.py``), prints the numbers compared beside their
limits on standard error, and prints one JSON line last on standard output:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
(from a profiler trace of a slice of the window) with ``--trace 1``.

Everything the run writes goes under ``bench_out/`` in the checkout: the
compilation cache, the service's data directory, the request records and
the traced spans.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "bench_out"


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def _cache_env() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``repro.compile_cache`` takes the directory from the environment),
    for every program, whatever its compile time or size."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(OUT / "jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


class NoChip(RuntimeError):
    pass


def device_info(chips: int, registry) -> dict:
    """The devices JAX found; raises ``NoChip`` unless they are TPUs of a
    kind ``bench/peaks.json`` lists, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found {info['platform']!r}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{info['count']}")
    if registry.peaks(info["kind"]) is None:
        raise NoChip(f"device kind {info['kind']!r} has no entry in "
                     "bench/peaks.json")
    info["count"] = chips
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    _cache_env()
    from bench.lib.cell import run_cell
    from bench.lib.registry import Registry
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    reg = Registry(ROOT)
    try:
        reg.cell(args.workload)
        info = device_info(int(reg.cell(args.workload)["chips"]), reg)
    except (NoChip, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run_cell(reg, args.workload, args.seed, args.seconds,
                      bool(args.trace), device=info, t_process=T_START,
                      out=OUT)
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
