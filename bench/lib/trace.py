"""From a profiler trace to events, device busy time and the breakdown.

The reduction works on a flat list of events, ``{"plane", "line", "name",
"t", "d"}`` with times in nanoseconds from the trace's start: the device's
program executions (``plane == "device"``) and the benchmark's host spans
(``plane == "host"``, names starting ``bench.``, with the span's
``stats`` where it carries any).  ``events_from_xplane``
makes that list from the ``.xplane.pb`` file that ``jax.profiler`` writes;
the tests run the rest on a small recorded list.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

SPAN_PREFIX = "bench."
# device lines that hold one event per program execution, by preference
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


def events_from_xplane(trace_dir: str) -> List[Dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    out: List[Dict] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in MODULE_LINES + OP_LINES:
                kind = "device"
            elif not device and plane.name.startswith("/host:"):
                kind = "host"
            else:
                continue
            for e in line.events:
                if kind == "host" and not e.name.startswith(SPAN_PREFIX):
                    continue
                ev = {"plane": kind, "device": plane.name,
                      "line": line.name, "name": e.name,
                      "t": float(e.start_ns), "d": float(e.duration_ns)}
                if kind == "host":
                    stats = dict(e.stats)
                    if stats:
                        ev["stats"] = stats
                out.append(ev)
    return out


def spans(events: Iterable[Dict], name: str) -> List[Dict]:
    return [e for e in events
            if e["plane"] == "host" and e["name"] == SPAN_PREFIX + name]


def device_events(events: Iterable[Dict], lines=MODULE_LINES) -> List[Dict]:
    return [e for e in events if e["plane"] == "device" and e["line"] in lines]


def program_name(event_name: str) -> str:
    """``jit_bank_pick(123)`` and the like -> ``bank_pick``."""
    m = re.match(r"(?:jit_)?([A-Za-z0-9_]+)", event_name)
    return m.group(1) if m else event_name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_intervals(events: List[Dict], window_ns: float
                   ) -> Dict[str, List[Tuple[float, float]]]:
    """Per device, the union of the intervals in which an operation runs
    (op lines where the trace has them, else program executions), cut to
    the traced window ``[0, window_ns]``."""
    per: Dict[str, List[Tuple[float, float]]] = {}
    ops = device_events(events, OP_LINES) or device_events(events)
    for e in ops:
        a, b = max(e["t"], 0.0), min(e["t"] + e["d"], window_ns)
        if b > a:
            per.setdefault(e["device"], []).append((a, b))
    return {k: _union(v) for k, v in per.items()}


def busy_s(events: List[Dict], window_ns: float) -> float:
    """Device busy seconds in the traced window, averaged over the
    devices that ran anything."""
    per = busy_intervals(events, window_ns)
    if not per:
        return 0.0
    tot = sum(b - a for iv in per.values() for a, b in iv)
    return tot / len(per) / 1e9


def breakdown(events: List[Dict], window_ns: float,
              top: int = 10) -> Dict[str, List]:
    """The programs that took most device time, and the device's idle
    time by what the host was doing: each gap between busy intervals is
    labelled by the innermost benchmark span open at its midpoint (lock
    waits only where nothing else is open)."""
    prog: Dict[str, float] = {}
    for e in device_events(events):
        prog[program_name(e["name"])] = prog.get(
            program_name(e["name"]), 0.0) + e["d"] / 1e9
    host = [e for e in events if e["plane"] == "host"]
    gaps: Dict[str, float] = {}
    per = busy_intervals(events, window_ns)
    iv = next(iter(per.values())) if per else []
    edges = [0.0] + [x for a, b in iv for x in (a, b)] + [window_ns]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [e for e in host if e["t"] <= mid <= e["t"] + e["d"]]
        work = [e for e in open_ if e["name"] != SPAN_PREFIX + "lock_wait"]
        pick = min(work or open_, key=lambda e: e["d"], default=None)
        label = (pick["name"][len(SPAN_PREFIX):] if pick
                 else "no request in service")
        gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9
    order = sorted(prog.items(), key=lambda kv: -kv[1])[:top]
    gorder = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in order],
            "idle_gaps": [[k, v] for k, v in gorder]}
