"""Least work of a kernel's call, from shapes, and the least time it
takes on a chip of ``peaks.json``.

The least bytes are those of the op's own inputs read once and of the
outputs that later ops or the client read written once, at the dtypes
the pipeline state holds them in; the least operations count one pass
of the arithmetic the op defines.  So a kernel's roofline share reads
the same work whatever implements it, and cannot pass 100 % unless the
time leaves out part of the work.
"""

from __future__ import annotations


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Seconds, and which bound sets them (``"bytes"`` or ``"flops"``)."""
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    t_flops = flops / peak["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def morph_recon(side: int) -> tuple[float, float]:
    """``recon_to_nuclei``'s reconstruction on a ``side``² tile: ``gray``
    (float32) read once, ``recon`` (float32) written once; one 3x3
    dilation and one clamp per pixel."""
    px = side * side
    return 10.0 * px, (4 + 4) * px


def feature_fused(side: int) -> tuple[float, float]:
    """The fused feature pass on a ``side``² tile: the RGB tile (uint8,
    the narrowest the state holds before the op) read once; ``hema``,
    ``eosin`` and the Sobel magnitude (float32), which the per-object
    reductions and the client read, written once.  Per pixel: three
    optical densities, two 3-term stain sums, the gray value, a 3x3
    Sobel pair and its magnitude."""
    px = side * side
    return 40.0 * px, (3 + 3 * 4) * px
