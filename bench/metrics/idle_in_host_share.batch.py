"""Share of the traced window's device-idle time that falls inside the
lanes' host phases: ``lane:gather``, ``op:*`` (the dispatch) and
``lane:commit`` less its download (device layer)."""

from bench.spans import HOST_PHASES, idle_share


def read(run):
    return idle_share(run, HOST_PHASES)
