"""Share of its roofline that the Pallas reconstruction kernel
(``kernels.morph_recon``) reaches: the least time of the
``recon_to_nuclei`` calls of the traced window (``bench/roofline.py``)
over the device time of the kernel's ops."""

from bench import roofline
from bench.metrics._stages import kernel_share


def read(run):
    return kernel_share(run, "recon_to_nuclei", "morph_recon",
                        roofline.morph_recon)
