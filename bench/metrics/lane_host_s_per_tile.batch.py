"""Host seconds of the worker lanes per tile (worker lanes layer): the
window's growth of the lanes' ``gather``, ``dispatch``, ``d2h`` and
``commit`` phase counters (``worker.lane.<lane>.<phase>_ns``) over the
tiles' worth of ops run in it."""

from bench.spans import lane_ns, tiles


def read(run):
    ns, n = lane_ns(run, ("gather", "dispatch", "d2h", "commit")), tiles(run)
    if ns is None or not n:
        return None
    return ns * 1e-9 / n
