"""Share of the traced window in which no op ran, averaged over the
four chips (device layer): 1 - busy / window, busy the union of a
chip's op intervals."""

from bench.metrics._chips import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else sum(shares) / len(shares)
