"""Share of the lanes' time spent waiting for a ready op (coordinator
layer: does the lease window feed the lane?): the window's growth of the
``wait`` phase counters over that of all six phases, which tile the
lanes' time."""

from bench.spans import PHASES, lane_ns


def read(run):
    wait, total = lane_ns(run, ("wait",)), lane_ns(run, PHASES)
    if wait is None or not total:
        return None
    return 100.0 * wait / total
