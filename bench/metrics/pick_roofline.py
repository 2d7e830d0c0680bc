"""Share of its roofline reached by the GP family's chain from candidates
to picks (%): the least time the chip's peaks allow for the chain's work,
over the device time of whatever programs implement it.

The work is summed over the picks in the traced slice
(``bench/work/gp_pick_chain.py``), each at the rows its study really has
in the system, observations and pending, as the ``bench.pick_gp`` span
records them: the bucket's padding is no work.
"""
from bench.lib.trace import device_events, program_name, spans
from bench.lib.registry import load_work

PROGRAMS = ("bank_prescale_C", "bank_absorb", "bank_dist", "bank_exp",
            "bank_pick", "bank_cluster_pick")


def read(ctx):
    ev = [e for e in device_events(ctx["events"])
          if program_name(e["name"]) in PROGRAMS]
    picks = [s["stats"] for s in spans(ctx["events"], "pick_gp")
             if s.get("stats", {}).get("rows")]
    if not ev or not picks:
        return None
    work = load_work("gp_pick_chain")
    peak = ctx["peaks"]
    least = 0.0
    for st in picks:
        for na in str(st["rows"]).split(","):
            c = {"S": int(st["S"]), "na": int(na), "d": int(st["d"]),
                 "n": int(st["n"])}
            least += max(work.flops(**c) / peak["bf16_flops_per_s"],
                         work.nbytes(**c) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (sum(e["d"] for e in ev) / 1e9)
