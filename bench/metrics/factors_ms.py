"""Mean device time of one execution of the factor program
``bank_factors`` in the traced slice (ms): the Cholesky factors, their
inverse and the condition estimate of every GP-family row at the bucket."""
from bench.lib.trace import device_events, program_name


def read(ctx):
    d = [e["d"] for e in device_events(ctx["events"])
         if program_name(e["name"]) == "bank_factors"]
    return sum(d) / len(d) / 1e6 if d else None
