"""The reduction from a profiler trace to the per-layer metrics: on a
small trace recorded on a TPU v5e (the program executions and the
benchmark's host spans of a 3.3 s slice of an ``xgb32.zipf`` run), on a
hand-made one whose answers are known, and on a trace that JAX writes
here."""
import json
from pathlib import Path

import pytest

from bench.lib import trace as tr
from bench.lib.registry import Registry

REPO = Path(__file__).resolve().parents[2]
SLICE = Path(__file__).resolve().parent / "data" / "xgb32_zipf_slice.json"
WINDOW_NS = 3.3333852380000053e9
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def events():
    return json.loads(SLICE.read_text())


def _ctx(events):
    return {"events": events, "window_ns": WINDOW_NS, "peaks": PEAKS}


def _read(name, ctx):
    return Registry(REPO).reader(name)(ctx)


def test_busy_is_the_union_of_program_executions(events):
    dev = sorted((e["t"], min(e["t"] + e["d"], WINDOW_NS)) for e in events
                 if e["plane"] == "device" and e["t"] < WINDOW_NS)
    busy, end = 0.0, float("-inf")
    for a, b in dev:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert tr.busy_s(events, WINDOW_NS) == pytest.approx(busy / 1e9,
                                                     rel=1e-12)
    idle = _read("idle_share.tail", _ctx(events))
    assert idle == pytest.approx(100 * (1 - busy / WINDOW_NS), rel=1e-12)
    assert 0.0 < idle < 100.0


def test_span_means(events):
    def mean(name):
        d = [e["d"] for e in events if e["name"] == "bench." + name]
        return sum(d) / len(d) / 1e6

    ctx = _ctx(events)
    assert _read("journal_ms.tail", ctx) == pytest.approx(mean("journal"))
    assert _read("lock_wait_ms.sat", ctx) == pytest.approx(
        mean("lock_wait"))
    assert _read("obs_stage_ms.tail", ctx) == pytest.approx(
        mean("obs_stage"))
    assert _read("draw_ms.tail", ctx) == pytest.approx(
        mean("sample_columns") + mean("encode_columns"))


def test_pick_roofline_by_hand(events):
    chain = ("bank_prescale_C", "bank_absorb", "bank_dist", "bank_exp",
             "bank_pick", "bank_cluster_pick")
    ev = [e for e in events if e["plane"] == "device"
          and tr.program_name(e["name"]) in chain]
    # the recorded slice's pick spans predate the rows they carry: give
    # each its study's rows in the system, as a run's spans have them
    spans = [dict(e) for e in events if e["name"] == "bench.pick_gp"]
    assert len(spans) == 4
    rows = [517, 133, 520, 88]
    for e, r in zip(spans, rows):
        e["stats"] = {"rows": str(r), "S": 28800, "d": 12, "n": 4}
    others = [e for e in events if e["name"] != "bench.pick_gp"]
    S, d, n = 28800, 12, 4
    flops = sum(2 * S * na * d + 2 * S * na * na + 4 * S * na
                + (n - 1) * (2 * S * d + 2 * S * na) for na in rows)
    want = 100 * flops / 197e12 / (sum(e["d"] for e in ev) / 1e9)
    got = _read("pick_roofline.tail", _ctx(others + spans))
    assert got == pytest.approx(want, rel=1e-12)
    assert 0.0 < got < 100.0
    # without the rows there is nothing to read
    assert _read("pick_roofline.tail", _ctx(events)) is None


def test_breakdown(events):
    out = tr.breakdown(events, WINDOW_NS)
    progs = dict(out["device_ops"])
    assert list(progs)[0] == "fit_hypers_bank"
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(
        WINDOW_NS / 1e9 - tr.busy_s(events, WINDOW_NS), rel=1e-9)


def test_hand_made_trace():
    ev = [{"plane": "device", "device": "d0", "line": "XLA Modules",
           "name": "jit_bank_pick(1)", "t": 0.0, "d": 4e8},
          {"plane": "device", "device": "d0", "line": "XLA Modules",
           "name": "jit_bank_dist(2)", "t": 2e8, "d": 4e8},
          {"plane": "host", "device": "h", "line": "python",
           "name": "bench.obs_stage", "t": 6.5e8, "d": 3e8},
          {"plane": "host", "device": "h", "line": "python",
           "name": "bench.lock_wait", "t": 6.0e8, "d": 4e8}]
    # busy 0..0.6 s of a 1 s window; the gap 0.6..1.0 s has its midpoint
    # (0.8 s) inside the obs stage, which wins over the lock wait
    assert tr.busy_s(ev, 1e9) == pytest.approx(0.6)
    out = tr.breakdown(ev, 1e9)
    assert out["device_ops"] == [["bank_pick", 0.4], ["bank_dist", 0.4]]
    assert out["idle_gaps"] == [["obs_stage", pytest.approx(0.4)]]
    assert tr.program_name("jit_fit_hypers_bank(99)") == "fit_hypers_bank"


def test_nothing_to_read_gives_nothing():
    ctx = {"events": [], "window_ns": 1e9, "peaks": PEAKS}
    for name in ("idle_share.tail", "journal_ms.tail", "lock_wait_ms.tail",
                 "obs_stage_ms.tail", "draw_ms.tail", "pick_roofline.tail"):
        assert _read(name, ctx) is None


def test_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.journal"):
        jnp.ones(8).block_until_ready()
    with jax.profiler.TraceAnnotation("other"):
        pass
    jax.profiler.stop_trace()
    ev = tr.events_from_xplane(str(tmp_path))
    host = [e for e in ev if e["plane"] == "host"]
    assert [e["name"] for e in host] == ["bench.journal"]
    assert host[0]["d"] > 0
    assert tr.events_from_xplane(str(tmp_path / "none")) == []
