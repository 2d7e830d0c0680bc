"""Mean time of one journal append, fsync included, per journaled op (ms)."""
from bench.lib.trace import spans


def read(ctx):
    s = spans(ctx["events"], "journal")
    return sum(e["d"] for e in s) / len(s) / 1e6 if s else None
