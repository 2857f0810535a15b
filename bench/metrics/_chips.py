"""Idle share of each chip the cell's lanes drive (shared by the
four-chip readers)."""


def idle_shares(run):
    """Percent of the traced window in which no op ran, for each chip a
    lane of the configuration drives (a chip with no op in the trace is
    idle throughout); ``None`` where the run has no trace or its device
    ran nothing."""
    trace = run.trace
    if trace is None or trace.busy_s() <= 0:
        return None
    chips = sorted({index for _, index in run.config["lanes"]})
    window = trace.window[1] - trace.window[0]
    return [100.0 * (1.0 - sum(e - s for s, e in trace.busy(c)) / window)
            for c in chips]
