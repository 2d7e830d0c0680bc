"""Mean time drawing and encoding one ask's candidates (ms)."""
from bench.lib.trace import spans


def read(ctx):
    draw = spans(ctx["events"], "sample_columns")
    enc = spans(ctx["events"], "encode_columns")
    if not draw or not enc:
        return None
    return (sum(e["d"] for e in draw) / len(draw)
            + sum(e["d"] for e in enc) / len(enc)) / 1e6
