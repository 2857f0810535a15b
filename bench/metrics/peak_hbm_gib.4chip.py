"""Peak device memory of the run, ``peak_bytes_in_use`` of the fullest
of the four chips after the window (device layer), in GiB."""


def read(run):
    if run.memory_peak is None:
        return None
    return run.memory_peak / 2**30
