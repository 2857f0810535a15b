"""Pallas TPU kernel: fused feature megakernel (deconv + moments + Sobel).

The feature fan-out of the WSI pipeline reads the same tile three
times — color deconvolution, pixel statistics over the hematoxylin
plane, and gradient statistics over the luminance.  When the whole
fan-out lands on one accelerator, this kernel computes all three in a
single VMEM pass: every (stripe, W) block is read from HBM once and
yields the hema/eosin stain planes, the Sobel gradient magnitude of
the luminance, and the per-stripe partial moments of hema and |grad|
(sum, sum-of-squares, max), reduced on the host.  One HBM read instead
of three is exactly the memory-roofline move that makes fine-grain
chained ops competitive with a monolithic kernel.

Layout follows ``sobel_stats``: row-stripe blocking with a one-tile
halo per side (:mod:`repro.kernels.stencil`); channel planes are
separate (H, W) arrays so every load is a contiguous lane-aligned tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import DECONV_MATRIX, GRAY_WEIGHTS
from .sobel_stats import sobel_mag
from .stencil import TILE, halo_rows, lane_tile, stripe_specs

__all__ = ["feature_fused_pallas"]


def _od(x):
    return -jnp.log10((x.astype(jnp.float32) + 1.0) / 256.0)


def _gray(r, g, b):
    wr, wg, wb = GRAY_WEIGHTS
    return (
        wr * r.astype(jnp.float32)
        + wg * g.astype(jnp.float32)
        + wb * b.astype(jnp.float32)
    )


def _kernel(
    r_up, r_c, r_dn,
    g_up, g_c, g_dn,
    b_up, b_c, b_dn,
    hema_ref, eosin_ref, mag_ref, stats_ref,
    *, m, hb,
):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    rc, gc, bc = r_c[...], g_c[...], b_c[...]

    # Stain separation on the center stripe (pure VPU elementwise).
    odr, odg, odb = _od(rc), _od(gc), _od(bc)
    hema = m[0][0] * odr + m[0][1] * odg + m[0][2] * odb
    eosin = m[1][0] * odr + m[1][1] * odg + m[1][2] * odb
    hema_ref[...] = hema
    eosin_ref[...] = eosin

    # Sobel of the luminance with edge-replicated halo rows: the last
    # row of the tile above and the first of the tile below inside the
    # image, the stripe's own boundary row at the image border.
    gray_c = _gray(rc, gc, bc)
    above = jnp.where(
        i == 0,
        gray_c[:1, :],
        _gray(r_up[hb - 1:hb, :], g_up[hb - 1:hb, :], b_up[hb - 1:hb, :]),
    )
    below = jnp.where(
        i == n - 1,
        gray_c[-1:, :],
        _gray(r_dn[0:1, :], g_dn[0:1, :], b_dn[0:1, :]),
    )
    mag = sobel_mag(gray_c, above, below)
    mag_ref[...] = mag

    # Per-stripe partial moments, reduced on the host.
    stats_ref[...] = lane_tile([
        hema.sum(), (hema * hema).sum(), hema.max(),
        mag.sum(), (mag * mag).sum(), mag.max(),
    ])


@functools.partial(jax.jit, static_argnames=("stripe", "interpret"))
def feature_fused_pallas(
    r: jnp.ndarray,
    g: jnp.ndarray,
    b: jnp.ndarray,
    *,
    stripe: int = 32,
    interpret: bool = False,
):
    """Fused deconv + hema moments + Sobel-of-luminance moments.

    Returns ``(hema, eosin, mag, stats)`` with ``stats`` the 6-vector
    ``[h_sum, h_sumsq, h_max, g_sum, g_sumsq, g_max]`` — the contract
    of :func:`repro.kernels.ref.feature_fused_ref`.
    """
    h, w = r.shape
    bh = min(stripe, h)
    if h % bh:
        raise ValueError(f"height {h} not divisible by stripe {bh}")
    n = h // bh
    hb = halo_rows(r.dtype)
    up, mid, dn = stripe_specs(h, w, bh, hb)
    m = tuple(tuple(float(x) for x in row) for row in DECONV_MATRIX)
    plane = jax.ShapeDtypeStruct((h, w), jnp.float32)
    hema, eosin, mag, partial = pl.pallas_call(
        functools.partial(_kernel, m=m, hb=hb),
        grid=(n,),
        in_specs=[up, mid, dn] * 3,
        out_specs=(mid, mid, mid, pl.BlockSpec(TILE, lambda i: (i, 0))),
        out_shape=(
            plane,
            plane,
            plane,
            jax.ShapeDtypeStruct((n * TILE[0], TILE[1]), jnp.float32),
        ),
        interpret=interpret,
    )(r, r, r, g, g, g, b, b, b)
    partial = partial.reshape(n, TILE[0], TILE[1])[:, 0, :6]
    stats = jnp.stack(
        [
            partial[:, 0].sum(),
            partial[:, 1].sum(),
            partial[:, 2].max(),
            partial[:, 3].sum(),
            partial[:, 4].sum(),
            partial[:, 5].max(),
        ]
    )
    return hema, eosin, mag, stats
