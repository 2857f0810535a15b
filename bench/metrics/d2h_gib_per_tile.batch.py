"""Bytes the worker lanes downloaded from the device per tile, in GiB
(worker lanes layer): the window's growth of ``worker.d2h_bytes`` over
the tiles' worth of ops run in it."""

from bench.spans import counter_delta, tiles


def read(run):
    nbytes, n = counter_delta(run, ["worker.d2h_bytes"]), tiles(run)
    if nbytes is None or not n:
        return None
    return nbytes / 2**30 / n
