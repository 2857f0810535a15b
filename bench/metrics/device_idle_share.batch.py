"""Share of the traced window in which no op ran on the chip (device
layer): 1 - busy / window, busy the union of op intervals."""


def read(run):
    if run.trace is None or run.trace.busy_s() <= 0:
        return None
    return 100.0 * run.trace.idle_share()
