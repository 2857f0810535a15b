"""Lane balance (worker lanes): the idle share of the most idle chip
less that of the least idle one, in percentage points of the traced
window."""

from bench.metrics._chips import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else max(shares) - min(shares)
