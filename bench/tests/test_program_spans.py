"""The program's own spans (``mango.*``) beside the benchmark's: the
reduction keeps only the benchmark's host spans, so every reader and the
breakdown read what they read before the program wrote spans."""
from bench.lib import trace as tr


def test_program_spans_stay_out_of_the_reduction(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.tracing import span

    with jax.profiler.trace(str(tmp_path)):
        with span("mango.ask", rows=lambda: "12"):
            with jax.profiler.TraceAnnotation("bench.journal"):
                with span("mango.journal"):
                    jnp.ones(8).block_until_ready()
    ev = tr.events_from_xplane(str(tmp_path))
    assert [e["name"] for e in ev if e["plane"] != "device"] == [
        "bench.journal"]
    assert tr.spans(ev, "journal")[0]["d"] > 0
