"""Mean time in the bank's observation stage per GP-family ask (ms): the
gather, the fit schedule, the factors and their copy to the host, or the
cache hit when nothing was observed since the last ask."""
from bench.lib.trace import spans


def read(ctx):
    s = spans(ctx["events"], "obs_stage")
    return sum(e["d"] for e in s) / len(s) / 1e6 if s else None
