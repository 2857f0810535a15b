"""Pallas TPU kernel: Sobel gradient magnitude + moment statistics.

One fused pass: a 3x3 stencil (edge-replicated) producing |grad| plus
per-stripe partial moments (sum, sum-of-squares, max), reduced on the
host.  Fusing the statistics into the stencil pass halves HBM traffic
vs stencil-then-reduce — exactly the memory-roofline move the paper's
feature ops need.  Row-stripe blocking with a one-tile halo per side
(:mod:`repro.kernels.stencil`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .stencil import TILE, halo_rows, hshift, lane_tile, stripe_specs, vshift

__all__ = ["sobel_stats_pallas", "sobel_mag"]


def sobel_mag(c, above, below):
    """Sobel |grad| of an f32 stripe, edge-replicated at the image border.

    ``above``/``below`` are the rows beyond the stripe (the stripe's own
    edge row at the image border, matching jnp.pad mode="edge" in the
    oracle).  Terms are summed in the oracle's order."""
    up, dn = vshift(c, above, below)
    sl = {}
    for dy, plane in enumerate((up, c, dn)):
        lf, rt = hshift(plane, plane, plane)
        sl[dy, 0], sl[dy, 1], sl[dy, 2] = lf, plane, rt
    gx = (
        -1.0 * sl[0, 0] + 1.0 * sl[0, 2]
        - 2.0 * sl[1, 0] + 2.0 * sl[1, 2]
        - 1.0 * sl[2, 0] + 1.0 * sl[2, 2]
    )
    gy = (
        -1.0 * sl[0, 0] - 2.0 * sl[0, 1] - 1.0 * sl[0, 2]
        + 1.0 * sl[2, 0] + 2.0 * sl[2, 1] + 1.0 * sl[2, 2]
    )
    return jnp.sqrt(gx * gx + gy * gy)


def _kernel(up_ref, c_ref, dn_ref, mag_ref, stats_ref, *, hb):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    c = c_ref[...].astype(jnp.float32)
    above = jnp.where(i == 0, c[:1, :], up_ref[hb - 1:hb, :].astype(jnp.float32))
    below = jnp.where(
        i == n - 1, c[-1:, :], dn_ref[0:1, :].astype(jnp.float32)
    )
    mag = sobel_mag(c, above, below)
    mag_ref[...] = mag
    stats_ref[...] = lane_tile([mag.sum(), (mag * mag).sum(), mag.max()])


@functools.partial(jax.jit, static_argnames=("stripe", "interpret"))
def sobel_stats_pallas(
    gray: jnp.ndarray, *, stripe: int = 64, interpret: bool = False
):
    h, w = gray.shape
    bh = min(stripe, h)
    if h % bh:
        raise ValueError(f"height {h} not divisible by stripe {bh}")
    n = h // bh
    hb = halo_rows(gray.dtype)
    up, mid, dn = stripe_specs(h, w, bh, hb)
    mag, partial = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid=(n,),
        in_specs=[up, mid, dn],
        out_specs=(mid, pl.BlockSpec(TILE, lambda i: (i, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((h, w), jnp.float32),
            jax.ShapeDtypeStruct((n * TILE[0], TILE[1]), jnp.float32),
        ),
        interpret=interpret,
    )(gray, gray, gray)
    partial = partial.reshape(n, TILE[0], TILE[1])[:, 0, :3]
    stats = jnp.stack(
        [partial[:, 0].sum(), partial[:, 1].sum(), partial[:, 2].max()]
    )
    return mag, stats
