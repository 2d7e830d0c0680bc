"""Operations and bytes of one GP-family pick, candidates to picks.

``S`` candidates of ``d`` encoded dims against the ``na`` rows of the
study's system (its observations and pending trials, not the padded
bucket), ``n`` picks.  Counted: the distances (2 S na d), the posterior's products
``K L^-T`` (2 S na^2), its mean and variance sums (2 S na each) and, for
the ``n - 1`` batch downdates, one candidate-kernel row and one product
with the cached block each (2 S d + 2 S na).  Elementwise work (the
Matern polynomial, the exponential) is not counted.  Bytes are the least
the chain must move: the candidates in, the observations, both factors
and the standardized values in, the picks out, all float32.
"""


def flops(S, na, d, n, **_):
    return (2 * S * na * d + 2 * S * na * na + 4 * S * na
            + (n - 1) * (2 * S * d + 2 * S * na))


def nbytes(S, na, d, n, **_):
    return 4 * (S * d + na * d + 2 * na * na + 2 * na + n)
