"""Read a cell's correctness numbers over many seeds, the program's and
the control's, in one process: the readings that its limits in
``bench/limits/<cell>.json`` are set from.

    python bench/probe.py --workload <cell> --seeds 11,12 --control-seeds 13 \
        --seconds 10 [--out readings.jsonl]

Each seed is one whole run of the cell (``bench/lib/cell.py``) at the
cell's own load and sizes, with a window of ``--seconds``.  A control run
traces the GP programs at ``"default"`` precision (one bfloat16 pass on
the TPU) and judges the TPE asks on the picks of the bfloat16 reference.  One JSON line per run: the seed,
whether it was the control, and every number compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench.run import OUT, ROOT, NoChip, _cache_env, device_info  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    _cache_env()
    from repro.compile_cache import enable_compile_cache

    from bench.lib.cell import run_cell
    from bench.lib.registry import Registry
    enable_compile_cache()
    reg = Registry(ROOT)
    try:
        info = device_info(int(reg.cell(args.workload)["chips"]), reg)
    except NoChip as e:
        print(f"probe: {e}", file=sys.stderr)
        return 2
    runs = ([(s, False) for s in _seeds(args.seeds)]
            + [(s, True) for s in _seeds(args.control_seeds)])
    out = open(args.out, "a") if args.out else None
    for seed, control in runs:
        t = time.monotonic()
        res = run_cell(reg, args.workload, seed, args.seconds, False,
                       device=dict(info), t_process=t, out=OUT / "probe",
                       control=control)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "control": control, "correct": res["correct"],
                           "run_s": time.monotonic() - t,
                           "checks": res["checks"]})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
