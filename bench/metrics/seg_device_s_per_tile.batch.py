"""Device seconds of the segmentation programs (``repro.app`` segmentation ops,
found by their jit names) per tile's worth of segmentation ops run in the
traced window (ops layer)."""

from bench.metrics._stages import device_s_per_tile


def read(run):
    return device_s_per_tile(run, "segmentation")
