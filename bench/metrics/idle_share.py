"""Share of the traced slice in which no operation ran on the device (%)."""
from bench.lib.trace import busy_s


def read(ctx):
    busy = busy_s(ctx["events"], ctx["window_ns"])
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / (ctx["window_ns"] / 1e9))
