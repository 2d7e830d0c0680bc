"""Mean wait to take the service's one lock, per request (ms)."""
from bench.lib.trace import spans


def read(ctx):
    s = spans(ctx["events"], "lock_wait")
    return sum(e["d"] for e in s) / len(s) / 1e6 if s else None
