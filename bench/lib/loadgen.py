"""The load generator's process: ``python bench/lib/loadgen.py <job.json>``.

It talks to the service over localhost HTTP through ``ServiceClient``, as
remote workers do, and never imports JAX, so it holds no chip and its
timing shares no interpreter lock with the server.  The traffic kind named
in the job (``bench/generators/<kind>.py``) produces the requests; the
records go to the job's ``out`` file as JSON lines.
"""
from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.service.client import ServiceClient

    job = json.loads(Path(argv[1]).read_text())
    kind = importlib.import_module(f"bench.generators.{job['mix']['kind']}")
    timeout = job["t_end"] - job["t_start"] + job["grace_s"] + 30.0
    records = kind.run(job, lambda: ServiceClient(job["url"], timeout=timeout,
                                                  retries=0))
    with open(job["out"], "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")
    if "jax" in sys.modules:
        raise RuntimeError("the load generator imported JAX")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
