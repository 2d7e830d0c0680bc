"""Mean device time of one execution of the fit program
``fit_hypers_bank`` in the traced slice (ms): the hyperparameter fit of
the due rows at their row bucket."""
from bench.lib.trace import device_events, program_name


def read(ctx):
    d = [e["d"] for e in device_events(ctx["events"])
         if program_name(e["name"]) == "fit_hypers_bank"]
    return sum(d) / len(d) / 1e6 if d else None
