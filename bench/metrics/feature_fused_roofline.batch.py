"""Share of its roofline that the fused feature kernel
(``kernels.feature_fused``) reaches: the least time of the
``feature_fused`` calls of the traced window (``bench/roofline.py``)
over the device time of the kernel's ops."""

from bench import roofline
from bench.metrics._stages import kernel_share


def read(run):
    return kernel_share(run, "feature_fused", "feature_fused",
                        roofline.feature_fused)
