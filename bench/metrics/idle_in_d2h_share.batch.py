"""Share of the traced window's device-idle time that falls inside the
lanes' ``lane:d2h`` spans, the downloads of written-back outputs (device
layer)."""

from bench.spans import idle_share


def read(run):
    return idle_share(run, ("d2h",))
