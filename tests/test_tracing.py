"""The span helper and the counters of ``repro.tracing``: a span builds
nothing while no profiler session records, counters lose no update from
many threads, and a recorded trace of served requests holds the
service's spans, nested within each request."""
import sys
import threading
import time

from repro.tracing import Counters, span


def test_span_builds_no_stats_while_nothing_records():
    def boom():
        raise AssertionError("a stat was built with no profiler session")

    with span("mango.x", rows=boom) as sp:
        sp.set_metadata(req="r1")


def test_counters_lose_no_update_across_threads():
    c = Counters(("n",))
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(2000):
                c.add("n")
                c.add("m", 2)

        threads = [threading.Thread(target=work) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
    assert c.snapshot() == {"n": 32 * 2000, "m": 2 * 32 * 2000}
    c.clear()
    assert c.snapshot() == {"n": 0}


def test_timed_adds_the_elapsed_nanoseconds():
    c = Counters(("t",))
    t0 = time.perf_counter_ns()
    with c.timed("t"):
        time.sleep(0.01)
    took = time.perf_counter_ns() - t0
    assert 10_000_000 <= c.snapshot()["t"] <= took


def _program_spans(trace_dir):
    """``mango.*`` events of the newest trace under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):   # a line per thread
            for e in line.events:
                if e.name.startswith("mango."):
                    out.setdefault(e.name[len("mango."):], []).append(
                        {"line": thread, "t": e.start_ns,
                         "d": e.duration_ns, "stats": dict(e.stats)})
    for evs in out.values():
        evs.sort(key=lambda e: e["t"])
    return out


def test_served_requests_write_nested_spans(tmp_path):
    """A profiler session recording served requests holds the service's
    spans with their stats, nested within their request on one thread."""
    import jax

    from repro.service.client import ServiceClient
    from repro.service.server import serve

    cfg = {"space": {"x": {"uniform": [0.0, 1.0]},
                     "y": {"uniform": [0.0, 1.0]}},
           "max_studies": 2, "seed": 0, "mc_samples": 32, "fit_steps": 2}
    httpd, svc = serve(tmp_path / "svc", port=0, config=cfg)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    # the traced ask compiles the bank's programs: no retry on a timeout
    cl = ServiceClient(f"http://127.0.0.1:{httpd.server_address[1]}",
                       timeout=600.0, retries=0)
    try:
        cl.create_study("gp")
        cl.create_study("tpe", optimizer="tpe")
        for name in ("gp", "tpe"):
            for v in (0.2, 0.7):
                cl.observe(name, {"x": v, "y": v}, v)
        cl.ask("tpe", n=2)
        with jax.profiler.trace(str(tmp_path / "tr")):
            with span("mango.probe", rows=lambda: "34", S=8) as sp:
                sp.set_metadata(req="r1")
            cl.ask("gp", n=2, req_id="h1")
            cl.ask("tpe", n=2)
    finally:
        httpd.shutdown()
        svc.close()
    prog = _program_spans(str(tmp_path / "tr"))
    # numeric strings come back as numbers from the trace's stats
    assert prog["probe"][0]["stats"] == {"rows": 34, "S": 8, "req": "r1"}
    outer = next(e for e in prog["http"] if e["stats"]["req"] == "h1")
    assert outer["stats"]["verb"] == "ask"
    assert outer["stats"]["study"] == "gp"

    def inside(e):
        return e["line"] == outer["line"] and outer["t"] <= e["t"] <= (
            e["t"] + e["d"]) <= outer["t"] + outer["d"]

    for name in ("ask", "lock_wait", "commit", "journal", "sample_columns",
                 "encode_columns", "obs_stage", "gather", "fit", "factors",
                 "pick_gp"):
        assert any(inside(e) for e in prog[name]), name
    assert "factors_copy" not in prog
    assert prog["commit"][0]["stats"] == {"op": "ask",
                                          "seq": svc.bank.op_seq - 1}
    assert prog["obs_stage"][0]["stats"] == {"hit": 0}
    assert prog["fit"][0]["stats"] == {"rows_due": 1, "rows_run": 1,
                                       "na": 16}
    assert prog["factors"][0]["stats"] == {"na": 16, "rows": 1, "obs": 2}
    assert prog["pick_gp"][0]["stats"] == {"rows": 2, "S": 32, "d": 2,
                                           "n": 2}
    assert len(prog["pick_tpe"]) == 1
